"""Host execute and row filter (``core/multi_query.py`` ``_execute_wave``):
mean duration of a ``wave.filter`` span, each query's host gather
(``TierStack.get_many``) and predicate mask for one round.  Moves ``p50_ms``."""


def read(ctx):
    ms = [1e3 * (t1 - t0) for t0, t1, _ in ctx.spans.get("wave.filter", [])]
    return sum(ms) / len(ms) if ms else None
