"""Serve any-k (LIMIT) queries on a TPU at the paper's §7.1 scale, and check
every answer against a plain full scan.

    python chip_smoke.py [--seed N]          # one chip (the default)
    python chip_smoke.py --four-chips        # λ-sharded planner on a 4-chip mesh

One chip: builds the §7.1 synthetic table (100M records, 8 binary dims at 10%
density, 2 f32 measures, 8192-record blocks; ``repro.configs.needletail_synth``)
from ``--seed``, places the block store and the DensityMap index on the chip,
and serves a few dozen exemplar requests (AND / OR of 1-3 predicates, k from
100 to 100,000) and a few online aggregates through
``ServeEngine.run_continuous`` with the device-resident planner over an HBM
tier stack.  Every exemplar answer is checked against a numpy full scan of the
table (each row satisfies the predicate, no duplicates, at least min(k,
matches) rows, measures match), a subset byte for byte against the sequential
``NeedleTailEngine.any_k``; every aggregate's estimate must lie within 4
standard errors of the full-scan mean.

Four chips: the same table and requests served through
``ServeEngine(exemplar_mesh=...)`` on a 4-device mesh, compared byte for byte
with the one-device device-wave run in the same process.

Everything runs in this one process: a chip belongs to one process at a time.
Each phase prints its time; the last line of stdout is
``{"ok": true, "device": {...}}``.  The script exits non-zero, and prints no
such line, when JAX finds no TPU, when ``PALLAS_INTERPRET`` asks for Pallas
interpret mode, or when any phase or check fails.  The persistent compile
cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``<repo>/.jax_cache`` (``repro.compile_cache``).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SLOTS = 64  # serving slots: the device wave plans [64, λ] per round
HBM_TIER_BYTES = 1 << 30  # tier-0 budget (logical slab bytes) for fills
DRAM_TIER_BYTES = 4 << 30
K_VALUES = (100, 1_000, 10_000, 100_000)


@contextlib.contextmanager
def timed(timings: dict, name: str):
    t0 = time.perf_counter()
    yield
    timings[name] = time.perf_counter() - t0
    print(f"phase {name}: {timings[name]:.3f} s", flush=True)


class CompileCounter:
    """Counts XLA backend compiles and persistent-cache hits, via
    ``jax.monitoring`` listeners (process-wide; register once)."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple[int, float, int]:
        return self.compiles, self.compile_s, self.cache_hits


def make_requests(seed: int, num_dims: int):
    """Deterministic request mix: ``(predicates, k, op)`` exemplar requests
    and ``(predicates, op, measure, error_slo)`` aggregates.

    Templates cross AND / OR with 1-3 predicates (value 1 has density 0.1,
    value 0 density 0.9); k cycles through :data:`K_VALUES`.  Three-way ANDs
    of 1-bits match ~0.1% of the table, so their k stops at 10,000: at
    100,000 they would read nearly every block.
    """
    import numpy as np

    rng = np.random.default_rng(seed + 7919)
    templates = [
        ((1,), "and"), ((1, 1), "or"), ((1, 1), "and"), ((1, 0), "and"),
        ((1, 1, 1), "or"), ((1, 1, 0), "and"), ((1, 0, 0), "or"),
        ((1, 1, 1), "and"), ((0,), "and"),
    ]
    exemplar = []
    for i, (values, op) in enumerate(templates):
        for k in K_VALUES:
            if len(values) == 3 and op == "and" and sum(values) == 3:
                k = min(k, 10_000)
            attrs = rng.choice(num_dims, size=len(values), replace=False)
            preds = [(int(a), int(v)) for a, v in zip(attrs, values)]
            exemplar.append((preds, int(k), op))
    aggregates = []
    for values, op, slo in (((1,), "and", 0.5), ((1, 1), "or", 0.3),
                            ((1, 1), "and", 0.5), ((1, 0), "and", 0.4)):
        attrs = rng.choice(num_dims, size=len(values), replace=False)
        preds = [(int(a), int(v)) for a, v in zip(attrs, values)]
        aggregates.append((preds, op, int(rng.integers(0, 2)), slo))
    return exemplar, aggregates


class FullScan:
    """Plain numpy full scan of the table: the reference the served answers
    are checked against (independent of blocks, density maps and plans)."""

    def __init__(self, table):
        self.table = table
        self._eq: dict = {}

    def mask(self, preds, op: str):
        import numpy as np

        ms = []
        for a, v in preds:
            if (a, v) not in self._eq:
                self._eq[(a, v)] = self.table.dims[:, a] == v
            ms.append(self._eq[(a, v)])
        return np.logical_and.reduce(ms) if op == "and" else np.logical_or.reduce(ms)


def check_exemplar(res, preds, k: int, op: str, scan: FullScan, rpb: int) -> dict:
    """Every returned row satisfies the predicate, none repeats, at least
    min(k, matches) come back, and the measures are the table's."""
    import numpy as np

    m = scan.mask(preds, op)
    matches = int(m.sum())
    idx = res.record_block.astype(np.int64) * rpb + res.record_row.astype(np.int64)
    n = scan.table.num_records
    if idx.size and (idx.min() < 0 or idx.max() >= n):
        raise AssertionError(f"{preds} {op} k={k}: row index outside the table")
    if np.unique(idx).size != idx.size:
        raise AssertionError(f"{preds} {op} k={k}: duplicate rows returned")
    if not m[idx].all():
        raise AssertionError(f"{preds} {op} k={k}: a returned row fails the predicate")
    if idx.size < min(k, matches):
        raise AssertionError(
            f"{preds} {op} k={k}: {idx.size} rows < min(k, matches={matches})")
    if not np.array_equal(res.measures, scan.table.measures[idx]):
        raise AssertionError(f"{preds} {op} k={k}: measures differ from the table")
    return {"rows": int(idx.size), "matches": matches,
            "blocks": int(res.blocks_fetched.size), "rounds": res.plan_rounds}


def check_aggregate(req, scan: FullScan) -> dict:
    """The estimate's interval, widened to ±4 standard errors, covers the
    full-scan mean.  The 1e-6 relative term absorbs float32 summation only:
    a design that read every matching block reports SE 0."""
    import numpy as np

    m = scan.mask(req.predicates, req.op)
    truth = float(np.mean(scan.table.measures[m, req.measure], dtype=np.float64))
    est = req.result
    err = abs(est.mean - truth)
    if not err <= 4.0 * est.se_mean + 1e-6 * abs(truth):
        raise AssertionError(
            f"aggregate {req.predicates} {req.op}: mean {est.mean} vs full scan "
            f"{truth}, |err| {err} > 4 SE ({4.0 * est.se_mean})")
    return {"mean": est.mean, "truth": truth, "se": est.se_mean,
            "reason": req.reason, "rounds": req.rounds}


def build_store(seed: int, num_records: int, records_per_block: int, timings: dict):
    """Generate the §7.1 table and place its block store on the default
    device; returns ``(table, store)``."""
    import jax

    from repro.configs.needletail_synth import CONFIG
    from repro.core.density_map import build_density_maps
    from repro.data.block_store import BlockStore, blocked_layout
    from repro.data.synthetic import make_clustered_table

    with timed(timings, "generate"):
        table = make_clustered_table(
            num_records=num_records, num_dims=CONFIG.num_dims,
            num_measures=CONFIG.num_measures, density=CONFIG.density, seed=seed,
        )
    with timed(timings, "index"):
        index = build_density_maps(table.dims, table.cards, records_per_block)
        jax.block_until_ready(index.densities)
    with timed(timings, "upload"):
        dims, meas, valid = blocked_layout(table.dims, table.measures, records_per_block)
        store = BlockStore(dims, meas, valid, index, records_per_block, table.num_records)
        jax.block_until_ready((store.dims_dev, store.meas_dev))
    return table, store


def serve(engine, server, exemplar, aggregates):
    """Submit every request, run the continuous loop to empty, return the
    completed ``(exemplar_requests, aggregate_requests)``."""
    ex = [server.submit_exemplar_request(p, k, op) for p, k, op in exemplar]
    ag = [
        server.submit_aggregate_request(p, measure, k=2_000, op=op, error_slo=slo)
        for p, op, measure, slo in aggregates
    ]
    server.run_continuous(engine)
    for r in ex + ag:
        if not r.done:
            raise AssertionError(f"request {r.rid} never completed")
    return ex, ag


def serve_and_check(store, table, seed: int, identity_every: int = 3) -> dict:
    """The one-chip serving phase and its checks; returns a summary."""
    import numpy as np

    from repro.core.engine import NeedleTailEngine
    from repro.serving.engine import ServeEngine
    from repro.storage import make_tier_stack

    stack = make_tier_stack(HBM_TIER_BYTES, DRAM_TIER_BYTES)
    engine = NeedleTailEngine(store, tiers=stack)
    server = ServeEngine(cfg=None, params=None, max_slots=SLOTS, exemplar_device=True)
    exemplar, aggregates = make_requests(seed, store.dims.shape[-1])
    t0 = time.perf_counter()
    ex, ag = serve(engine, server, exemplar, aggregates)
    serve_s = time.perf_counter() - t0
    scan = FullScan(table)
    rpb = store.records_per_block
    rows = [check_exemplar(r.result, p, k, op, scan, rpb)
            for r, (p, k, op) in zip(ex, exemplar)]
    identical = 0
    for i in range(0, len(ex), identity_every):
        p, k, op = exemplar[i]
        ref = engine.any_k(p, k, op)
        got = ex[i].result
        for name in ("record_block", "record_row", "measures", "blocks_fetched"):
            if not np.array_equal(getattr(ref, name), getattr(got, name)):
                raise AssertionError(
                    f"request {i} {p} {op} k={k}: {name} differs from any_k")
        identical += 1
    aggs = [check_aggregate(r, scan) for r in ag]
    tiers = stack.tier_counters()
    return {
        "serve_s": serve_s, "exemplar": len(ex), "aggregates": len(ag),
        "identical_to_any_k": identical,
        "rows_returned": sum(r["rows"] for r in rows),
        "blocks_read": sum(r["blocks"] for r in rows),
        "max_rounds": max(r["rounds"] for r in rows),
        "hbm_admissions": tiers["hbm.admissions"],
        "hbm_hits": tiers["hbm.hits"],
        "aggregate_checks": aggs,
    }


def _device_record(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def _require_chip(min_devices: int):
    """Refuse every path that would hide the device."""
    flag = os.environ.get("PALLAS_INTERPRET")
    if flag is not None and flag not in ("0", "false", "False"):
        raise SystemExit(f"PALLAS_INTERPRET={flag} forces interpret mode; refusing")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devices[0].platform!r} devices")
    if len(devices) < min_devices:
        raise SystemExit(f"need {min_devices} TPU devices, JAX found {len(devices)}")
    return devices


def one_chip(seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs.needletail_synth import CONFIG
    from repro.core.engine import NeedleTailEngine
    from repro.core.multi_query import DeviceWave
    from repro.data.block_store import _gather_lane_dense

    devices = _require_chip(1)
    counter = CompileCounter()
    timings: dict = {}
    rpb = CONFIG.records_per_block
    table, store = build_store(seed, CONFIG.num_records, rpb, timings)
    dev = devices[0]
    logical = int(store.dims_dev.nbytes + store.meas_dev.nbytes)
    in_use = dev.memory_stats()["bytes_in_use"]
    print(f"lambda={store.num_blocks} records={store.num_records} "
          f"store_device_bytes={logical} bytes_in_use={in_use} "
          f"ratio={in_use / logical:.4f}", flush=True)
    if in_use > 1.1 * logical:
        raise AssertionError(f"device holds {in_use} B for a {logical} B store (>1.1x)")

    with timed(timings, "compile"):
        ids = jnp.zeros((256,), jnp.int32)
        text = _gather_lane_dense.lower(
            store.dims_dev, store.meas_dev, ids, jnp.int32(store.num_records),
            interpret=False,
        ).compile().as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError("block_gather did not compile to a native TPU kernel")
        # the served round program, compiled by one call on the empty wave
        wave = DeviceWave(NeedleTailEngine(store, cache_bytes=0), SLOTS)
        st = wave.state
        jax.block_until_ready(wave.round_fn(
            st.combined0, st.excl, st.th_mask, st.tp_win,
            jnp.asarray(wave.chosen), jnp.ones((wave.qb,), jnp.float32)))
    c0 = counter.snapshot()
    print(f"compile_events={c0[0]} compile_s={c0[1]:.3f} cache_hits={c0[2]}",
          flush=True)
    summary = serve_and_check(store, table, seed)
    c1 = counter.snapshot()
    timings["serve"] = summary.pop("serve_s")
    print(f"phase serve: {timings['serve']:.3f} s", flush=True)
    aggs = summary.pop("aggregate_checks")
    print("serve " + json.dumps(summary), flush=True)
    for a in aggs:
        print("aggregate " + json.dumps(a), flush=True)
    print(f"serve_compile_events={c1[0] - c0[0]} serve_compile_s={c1[1] - c0[1]:.3f} "
          f"serve_cache_hits={c1[2] - c0[2]} peak_bytes_in_use="
          f"{dev.memory_stats().get('peak_bytes_in_use')}", flush=True)
    print("phases " + json.dumps({k: round(v, 3) for k, v in timings.items()}),
          flush=True)
    return _device_record(devices)


def four_chips(seed: int) -> dict:
    import jax
    import numpy as np

    from repro.configs.needletail_synth import CONFIG
    from repro.core.engine import NeedleTailEngine
    from repro.serving.engine import ServeEngine
    from repro.storage import make_tier_stack

    devices = _require_chip(4)[:4]
    timings: dict = {}
    table, store = build_store(seed, CONFIG.num_records, CONFIG.records_per_block,
                               timings)
    exemplar, _ = make_requests(seed, store.dims.shape[-1])
    mesh = jax.make_mesh((4,), ("data",), devices=devices)
    runs = {}
    for name, kwargs in (("one_device", {}), ("mesh", {"exemplar_mesh": mesh})):
        engine = NeedleTailEngine(
            store, tiers=make_tier_stack(HBM_TIER_BYTES, DRAM_TIER_BYTES))
        server = ServeEngine(cfg=None, params=None, max_slots=SLOTS,
                             exemplar_device=True, **kwargs)
        with timed(timings, f"serve_{name}"):
            ex, _ = serve(engine, server, exemplar, [])
        runs[name] = (ex, server, engine)
    scan = FullScan(table)
    for (p, k, op), a, b in zip(exemplar, runs["one_device"][0], runs["mesh"][0]):
        check_exemplar(b.result, p, k, op, scan, store.records_per_block)
        for field in ("record_block", "record_row", "measures", "blocks_fetched"):
            if not np.array_equal(getattr(a.result, field), getattr(b.result, field)):
                raise AssertionError(f"{p} {op} k={k}: mesh {field} differs")
    _, server, engine = runs["mesh"]
    if engine.distributed is None:
        raise AssertionError("the mesh run never attached the sharded planner")
    wave = server._exemplar_loop.dwave
    placed = {}
    for field in ("excl", "th_mask"):
        arr = getattr(wave.state, field)
        on = sorted({s.device.id for s in arr.addressable_shards})
        placed[field] = on
        if len(on) != 4:
            raise AssertionError(f"wave {field} lives on devices {on}, not all four")
    print("four_chips " + json.dumps({
        "requests": len(exemplar), "identical": len(exemplar),
        "wave_devices": placed, "mesh": dict(mesh.shape)}), flush=True)
    print("phases " + json.dumps({k: round(v, 3) for k, v in timings.items()}),
          flush=True)
    return _device_record(devices)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="serve through the λ-sharded planner on a 4-device mesh "
                         "and compare with the one-device run (no other phase)")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    print(f"compile_cache={enable_compile_cache()}", flush=True)
    device = four_chips(args.seed) if args.four_chips else one_chip(args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
