"""Device wave planner (``core/multi_query.py`` ``DeviceWave._flush_joins``):
mean duration of a ``plan.join`` span, the seating of a round's joiners
(host row matrix, combine and scatter programs).  Moves ``p50_ms``."""


def read(ctx):
    ms = [1e3 * (t1 - t0) for t0, t1, _ in ctx.spans.get("plan.join", [])]
    return sum(ms) / len(ms) if ms else None
