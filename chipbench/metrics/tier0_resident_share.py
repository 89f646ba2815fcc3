"""Tier stack (``storage/tiers.py``): percent of the blocks a round's fill
asked for that already sat in the HBM tier, the ``tier.fill`` spans' summed
``in_hbm`` over their summed ``union``.  Each block of a round counts once,
where it was before the fill.  Moves ``p50_ms``."""


def read(ctx):
    fills = [a for _, _, a in ctx.spans.get("tier.fill", []) if "union" in a]
    union = sum(a["union"] for a in fills)
    if union <= 0:
        return None
    return 100.0 * sum(a.get("in_hbm", 0) for a in fills) / union
