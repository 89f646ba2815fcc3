"""Device wave planner (``core/multi_query.py`` ``DeviceWave.plan_round``):
mean duration of a ``plan.decode`` span, the host's unpacking of the round's
plan, the §7.2 arbitration and the exclusion diff.  Moves ``p50_ms``."""


def read(ctx):
    ms = [1e3 * (t1 - t0) for t0, t1, _ in ctx.spans.get("plan.decode", [])]
    return sum(ms) / len(ms) if ms else None
