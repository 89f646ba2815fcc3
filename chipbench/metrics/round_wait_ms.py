"""Device wave planner (``core/multi_query.py`` ``DeviceWave.plan_round``):
mean duration of a ``plan.device_round`` span, from the round program's call
until its packed plan is on the host.  Moves ``p50_ms``."""


def read(ctx):
    ms = [1e3 * (t1 - t0) for t0, t1, _ in ctx.spans.get("plan.device_round", [])]
    return sum(ms) / len(ms) if ms else None
