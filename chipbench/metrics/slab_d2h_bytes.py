"""Host execute and row filter (``storage/tiers.py`` ``Tier.host_view``):
bytes of block slabs downloaded from the HBM tier to its host mirror for
the host row filter, per round: the ``hbm.host_download_bytes`` counter's
growth over the window, over the window's ``device.transfer`` events.  A
program without the counter reads nothing.  Moves ``p50_ms``."""
from chipbench.harness import delta

KEY = "hbm.host_download_bytes"


def probe(served):
    n = served.engine.block_cache.tier_counters().get(KEY)
    return {} if n is None else {"bytes": n}


def read(ctx):
    d = delta(ctx.probe, "open", "close")
    rounds = len(ctx.events.get("device.transfer", []))
    if "bytes" not in d or rounds == 0:
        return None
    return d["bytes"] / rounds
