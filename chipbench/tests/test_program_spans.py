"""The program's spans and counters inside the refill round, as the readers
in ``chipbench/metrics/`` that ``BENCHMARK.json`` does not list yet read them:
one traced run at a CPU test's size, its span tree, and the spans' mirror in
the profiler's host plane."""
import pytest

from chipbench import harness, trace
from chipbench.tests.tiny import tiny_cell, tiny_run

NEW = {"admit_wait_ms": "ms", "join_ms": "ms", "round_wait_ms": "ms", "decode_ms": "ms",
       "fill_ms": "ms", "tier0_resident_share": "%", "filter_ms": "ms",
       "slab_d2h_bytes": "B"}
MIRRORED = ("plan.device_round", "tier.fill")


@pytest.fixture(scope="module")
def traced():
    """One traced tiny run, with what the harness saw along the way: the
    system it built, the window's spans and events, and the profiler's host
    events and clock offset."""
    from repro.storage.tiers import TierStack

    got: dict = {}
    build, recorded = harness.build, harness._recorded
    reduce_trace, reduce = harness.reduce_trace, trace.reduce

    def spy_build(cell, seed, traced, timings):
        sv = build(cell, seed, traced, timings)
        got["sv"] = sv
        got["slab_bytes"] = TierStack.block_nbytes(sv.store)
        return sv

    def spy_recorded(obs, lo, hi):
        got["window"] = recorded(obs, lo, hi)
        return got["window"]

    def spy_reduce_trace(prof, marks, spans_all):
        got["bounds"] = tuple(prof.bounds)
        pd = trace.load(prof.dir)
        got["host"] = {n: trace.host_events(pd, n) for n in MIRRORED}
        return reduce_trace(prof, marks, spans_all)

    def spy_reduce(pd, window_ns=None):
        got["window_ns"] = window_ns
        return reduce(pd, window_ns)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "build", spy_build)
        mp.setattr(harness, "_recorded", spy_recorded)
        mp.setattr(harness, "reduce_trace", spy_reduce_trace)
        mp.setattr(trace, "reduce", spy_reduce)
        cell = tiny_cell("nt71-browse")
        cell.per_layer = cell.per_layer + [{"name": n, "unit": u} for n, u in NEW.items()]
        got["result"] = tiny_run("nt71-browse", traced=True, cell=cell)
    return got


def test_traced_run_reads_every_refill_round_metric(traced):
    res = traced["result"]
    assert res["correct"] is True, res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(NEW) <= set(m)
    assert 0 < m["tier0_resident_share"] <= 100
    for name in ("join_ms", "round_wait_ms", "decode_ms", "fill_ms", "filter_ms"):
        assert m[name] > 0
    # whole slabs per round: the counter moves one block's bytes at a time
    rounds = len(traced["window"][1]["device.transfer"])
    slabs = m["slab_d2h_bytes"] * rounds / traced["slab_bytes"]
    assert slabs == pytest.approx(round(slabs), abs=1e-6)


def test_refill_round_spans_nest_under_their_parents(traced):
    events = traced["sv"].obs.to_events()
    spans = {e["id"]: e for e in events if e["kind"] == "span"}
    names = {e["name"] for e in spans.values()}
    assert {"plan.round", "plan.join", "plan.device_round", "plan.decode",
            "wave.execute", "tier.fill", "wave.filter", "serve.step"} <= names
    parent_of = {"plan.join": "plan.round", "plan.device_round": "plan.round",
                 "plan.decode": "plan.round", "tier.fill": "wave.execute",
                 "wave.filter": "wave.execute", "serve.exemplar_tick": "serve.step"}
    for e in spans.values():
        want = parent_of.get(e["name"])
        if want is not None:
            assert spans[e["parent"]]["name"] == want, e
    rounds = [e for e in spans.values() if e["name"] == "plan.round"
              and e.get("attrs", {}).get("n_active")]
    assert rounds and all(e["attrs"]["site"] == "device" for e in rounds)
    fills = [e["attrs"] for e in spans.values() if e["name"] == "tier.fill"]
    assert all(a["in_hbm"] + a["in_dram"] + a["from_store"] == a["union"] for a in fills)


def test_profiler_host_plane_holds_the_spans_on_the_recorders_clock(traced):
    offset = traced["window_ns"][0] - traced["bounds"][0] * 1e9
    spans = traced["window"][0]
    for name in MIRRORED:
        host = traced["host"][name]
        mine = spans.get(name, [])
        assert host and mine, name
        for t0, t1, _ in mine:
            s, e = min(host, key=lambda iv: abs(iv[0] - (t0 * 1e9 + offset)))
            assert abs(s - (t0 * 1e9 + offset)) < 1e6, name
            assert abs(e - (t1 * 1e9 + offset)) < 1e6, name
