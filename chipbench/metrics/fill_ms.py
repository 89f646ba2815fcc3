"""Tier stack (``storage/tiers.py`` ``TierStack.ensure``, called from
``core/multi_query.py`` ``_execute_wave``): mean duration of a ``tier.fill``
span, one per round with blocks to read.  Moves ``p50_ms``."""


def read(ctx):
    ms = [1e3 * (t1 - t0) for t0, t1, _ in ctx.spans.get("tier.fill", [])]
    return sum(ms) / len(ms) if ms else None
