"""Unified observability plane: TraceRecorder, MetricsRegistry, the closed
wave-stats schema, and the offline trace report.

The contract under test (see ``src/repro/obs/``): one recorder threaded
through the serving stack emits a single structured stream a tool can turn
back into per-request critical paths — while a *disabled* recorder costs
zero clock reads and zero buffered events on the hot path, and tracing
never steers: results are byte-identical with the recorder on or off.
"""
import json

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.engine import NeedleTailEngine
from repro.core.multi_query import BatchQuery
from repro.data.block_store import build_block_store
from repro.data.synthetic import make_clustered_table
from repro.obs import (
    NULL_SPAN, MetricsRegistry, TraceRecorder, WAVE_STATS_KEYS,
    make_wave_stats, record_wave_metrics,
)
from repro.serving.admission import AdmissionPolicy
from repro.serving.engine import ServeEngine

pytestmark = pytest.mark.serving

RPB = 64


class CountingClock:
    def __init__(self, t: float = 0.0, dt: float = 0.001):
        self.t = t
        self.dt = dt
        self.calls = 0

    def __call__(self) -> float:
        self.calls += 1
        self.t += self.dt
        return self.t


_STORE_CACHE: dict = {}


def _get_store():
    if "store" not in _STORE_CACHE:
        t = make_clustered_table(num_records=6_000, num_dims=4, density=0.15,
                                 seed=11)
        _STORE_CACHE["store"] = build_block_store(t, records_per_block=RPB)
    return _STORE_CACHE["store"]


@pytest.fixture(scope="module")
def store():
    return _get_store()


def _queries():
    return [BatchQuery([(0, 1)], 40), BatchQuery([(0, 1), (1, 1)], 80),
            BatchQuery([(2, 1)], 25, "and")]


# ---------------------------------------------------------------------------
# TraceRecorder core: nesting, ids, ring buffer, export.
# ---------------------------------------------------------------------------
def test_span_nesting_and_parents():
    clk = CountingClock()
    rec = TraceRecorder(clock=clk)
    with rec.span("outer", q=1) as outer:
        rec.event("point", x=2)
        with rec.span("inner"):
            pass
        outer.set(late=3)
    events = rec.to_events()
    names = [(e["kind"], e["name"]) for e in events]
    # spans emit on EXIT: inner closes before outer
    assert names == [("event", "point"), ("span", "inner"), ("span", "outer")]
    point, inner, outer = events
    assert point["parent"] == outer["id"]
    assert inner["parent"] == outer["id"]
    assert outer["parent"] == 0
    assert outer["attrs"] == {"q": 1, "late": 3}
    assert outer["t0"] < inner["t0"] < inner["t1"] < outer["t1"]
    # exactly two clock reads per span, one per event
    assert clk.calls == 2 * 2 + 1


def test_deterministic_ids_and_ring_buffer():
    def stream(rec):
        for i in range(8):
            with rec.span("s", i=i):
                rec.event("e", i=i)
        return [(e["id"], e["name"]) for e in rec.to_events()]

    a, b = TraceRecorder(clock=CountingClock()), TraceRecorder(clock=CountingClock())
    assert stream(a) == stream(b)  # one monotonic id counter => same stream

    small = TraceRecorder(clock=CountingClock(), max_events=5)
    stream(small)
    assert len(small.events) == 5
    assert small.dropped == 16 - 5  # overflow is counted, never silent


def test_export_jsonl_round_trips(tmp_path):
    rec = TraceRecorder(clock=CountingClock())
    with rec.span("tick"):
        rec.event("fetch", n=3)
    path = rec.export_jsonl(str(tmp_path / "t.jsonl"))
    lines = [json.loads(line) for line in open(path)]
    assert lines == rec.to_events()
    # sorted keys: identical runs produce identical bytes modulo timestamps
    assert open(path).readline().startswith('{"attrs"')


# ---------------------------------------------------------------------------
# Disabled is free: zero clock reads, zero events, the shared null span.
# ---------------------------------------------------------------------------
def test_disabled_recorder_is_free():
    clk = CountingClock()
    rec = TraceRecorder(clock=clk, enabled=False)
    for i in range(50):
        span = rec.span("hot", i=i)
        assert span is NULL_SPAN  # one shared instance, no allocation
        with span as s:
            assert s.set(x=1) is NULL_SPAN
            rec.event("hot.point", i=i)
    assert clk.calls == 0
    assert len(rec.events) == 0
    assert rec.dropped == 0


def test_disabled_recorder_through_full_serving_run(store):
    clk = CountingClock()
    rec = TraceRecorder(clock=clk, enabled=False)
    eng = NeedleTailEngine(store, obs=rec)
    serve = ServeEngine(None, None, max_slots=2,
                        exemplar_policy=AdmissionPolicy(max_wave=2),
                        obs=rec)
    reqs = [serve.submit_exemplar_request(q.predicates, q.k)
            for q in _queries()]
    for _ in range(64):
        if all(r.done for r in reqs):
            break
        serve.exemplar_tick(eng, drain=True)
    assert all(r.done for r in reqs)
    assert clk.calls == 0, "disabled recorder read the clock on the hot path"
    assert len(rec.events) == 0


# ---------------------------------------------------------------------------
# Tracing observes, never steers: byte-identical results on and off.
# ---------------------------------------------------------------------------
@settings(max_examples=8, deadline=None)
@given(st.integers(0, 50), st.sampled_from([16, 64, 200]))
def test_any_k_batch_byte_identical_traced(seed, k):
    store = _get_store()
    rng = np.random.default_rng(seed)
    dim = int(rng.integers(0, 4))
    queries = [BatchQuery([(dim, 1)], k), BatchQuery([(0, 1), (1, 1)], k, "and")]
    plain = NeedleTailEngine(store).any_k_batch(queries, algo="auto")
    rec = TraceRecorder(clock=CountingClock())
    traced = NeedleTailEngine(store, obs=rec).any_k_batch(queries, algo="auto")
    for a, b in zip(plain.results, traced.results):
        np.testing.assert_array_equal(a.record_block, b.record_block)
        np.testing.assert_array_equal(a.record_row, b.record_row)
        np.testing.assert_array_equal(a.measures, b.measures)
    assert any(e["name"] == "batch.run" for e in rec.to_events())


def test_anyk_round_spans_carry_plan_attrs(store):
    rec = TraceRecorder(clock=CountingClock())
    eng = NeedleTailEngine(store, obs=rec)
    eng.any_k([(0, 1)], 64, algo="auto")
    rounds = [e for e in rec.to_events()
              if e["kind"] == "span" and e["name"] == "anyk.round"]
    assert rounds
    for e in rounds:
        a = e["attrs"]
        assert a["algo"] in ("threshold", "two_prong")
        assert a["predicted_io_s"] >= 0.0
        assert a["n_blocks"] >= 0


# ---------------------------------------------------------------------------
# One wave-stats schema across every pool.
# ---------------------------------------------------------------------------
def test_make_wave_stats_schema_is_closed():
    s = make_wave_stats("exemplar", wave_size=3)
    assert tuple(s.keys()) == WAVE_STATS_KEYS
    with pytest.raises(ValueError, match="unknown wave-stats"):
        make_wave_stats("exemplar", wave_sz=3)


def test_wave_stats_schema_consistent_across_pools(store):
    eng = NeedleTailEngine(store)
    serve = ServeEngine(None, None, max_slots=2,
                        exemplar_policy=AdmissionPolicy(max_wave=2))
    key_sets = {}

    reqs = [serve.submit_exemplar_request(q.predicates, q.k)
            for q in _queries()[:2]]
    for _ in range(64):
        if all(r.done for r in reqs):
            break
        serve.exemplar_tick(eng, drain=True)
    assert all(r.done for r in reqs)
    key_sets["exemplar"] = tuple(serve.last_wave_stats.keys())
    assert serve.last_wave_stats["kind"] == "exemplar"

    agg = serve.submit_aggregate_request([(0, 1)], 0, 200, error_slo=0.5)
    for _ in range(64):
        if agg.done:
            break
        serve.aggregate_tick(eng, drain=True)
    assert agg.done
    key_sets["aggregate"] = tuple(serve.last_wave_stats.keys())
    assert serve.last_wave_stats["kind"] == "aggregate"

    serve._note_lm_wave(2)  # the exact ledger writer lm_tick uses
    key_sets["lm"] = tuple(serve.last_wave_stats.keys())
    assert serve.last_wave_stats["kind"] == "lm"

    for kind, keys in key_sets.items():
        assert keys == WAVE_STATS_KEYS, f"{kind} diverged from the schema"


def test_record_wave_metrics_mirrors_ledger():
    m = MetricsRegistry()
    record_wave_metrics(m, make_wave_stats(
        "exemplar", wave_size=4, rounds=2, device_transfers=1,
        store_blocks_fetched=7, cache_hits=3, unique_blocks=9,
        tiers={"hbm_hits": 5}, slot_occupancy=0.5, plan_qerror=1.25,
        prefetch={"issued": 2}, pending=1))
    snap = m.snapshot()
    assert snap["counters"]["wave.exemplar.waves"] == 1
    assert snap["counters"]["wave.exemplar.store_blocks_fetched"] == 7
    assert snap["counters"]["tiers.hbm_hits"] == 5
    assert snap["counters"]["prefetch.issued"] == 2
    assert snap["gauges"]["wave.exemplar.slot_occupancy"] == 0.5
    assert m.quantile("wave.exemplar.wave_size", 0.5) == 4
    assert m.quantile("wave.exemplar.plan_qerror", 0.99) == 1.25


# ---------------------------------------------------------------------------
# MetricsRegistry: quantiles + prometheus text.
# ---------------------------------------------------------------------------
def test_metrics_registry_quantiles_and_render():
    m = MetricsRegistry()
    m.inc("requests", 3)
    m.set_gauge("occupancy", 0.75)
    for v in range(1, 101):
        m.observe("wait_s", v / 1000.0)
    assert m.counter("requests") == 3
    assert m.quantile("wait_s", 0.50) == pytest.approx(0.050)
    assert m.quantile("wait_s", 0.99) == pytest.approx(0.099)
    text = m.render_prometheus()
    assert "requests 3" in text
    assert "occupancy 0.75" in text
    assert "wait_s_count 100" in text
    assert "wait_s_p99 0.099" in text


# ---------------------------------------------------------------------------
# The offline report: critical paths from the JSONL alone.
# ---------------------------------------------------------------------------
def _traced_serving_run(store, tmp_path):
    from tools.trace_report import load_events

    clk = CountingClock(dt=0.0005)
    rec = TraceRecorder(clock=clk)
    eng = NeedleTailEngine(store)
    serve = ServeEngine(None, None, max_slots=2,
                        exemplar_policy=AdmissionPolicy(max_wave=2),
                        clock=clk, obs=rec)
    reqs = [serve.submit_exemplar_request(q.predicates, q.k)
            for q in _queries()]
    for _ in range(64):
        if all(r.done for r in reqs):
            break
        serve.exemplar_tick(eng, drain=True)
    assert all(r.done for r in reqs)
    path = rec.export_jsonl(str(tmp_path / "trace.jsonl"))
    return reqs, load_events(path)


def test_trace_report_reconstructs_every_request(store, tmp_path):
    from tools.trace_report import render, request_paths, wave_summary

    reqs, events = _traced_serving_run(store, tmp_path)
    paths = request_paths(events)
    assert sorted(paths) == sorted(r.rid for r in reqs)
    for r in paths.values():
        assert r["kind"] == "exemplar"
        assert r["reason"] in ("full_waves", "deadline_waves", "cheap_waves",
                               "resident_waves", "refill_waves", "flush_waves")
        assert r["ticks"] >= 1
        assert 0.0 <= r["wait_s"] <= r["wall_s"]
        # the span tree accounts for the request's wall latency (shared
        # virtual clock: waits + tick spans tile [submit, done] exactly)
        assert r["coverage"] >= 0.95

    summary = wave_summary(events)
    assert summary["spans"]["serve.exemplar_tick"]["count"] >= 1
    assert summary["launch_reasons"]
    report = render(events)
    assert "requests (critical path):" in report
    assert "serve.exemplar_tick" in report


def test_trace_report_merge_overlap():
    from tools.trace_report import _merge_overlap

    ivs = [(0.0, 2.0), (1.0, 3.0), (5.0, 6.0)]
    assert _merge_overlap(ivs, 0.0, 10.0) == pytest.approx(4.0)
    assert _merge_overlap(ivs, 2.5, 5.5) == pytest.approx(1.0)
    assert _merge_overlap([], 0.0, 1.0) == 0.0


def test_fetch_events_carry_predicted_vs_observed_io(store):
    from repro.storage import TierStack, make_tier_stack

    rec = TraceRecorder(clock=CountingClock())
    stack = make_tier_stack(4 * RPB * (4 * 4 + 2 * 4 + 1), None)
    eng = NeedleTailEngine(store, tiers=stack, obs=rec)
    eng.any_k_batch(_queries(), algo="auto")
    fetches = [e for e in rec.to_events() if e["name"] == "fetch.store"]
    assert fetches, "cold tiered wave must emit fetch.store events"
    for e in fetches:
        a = e["attrs"]
        assert a["n"] > 0
        assert a["predicted_io_s"] >= 0.0
        assert a["observed_io_s"] >= 0.0
        assert a["level"]


# ---------------------------------------------------------------------------
# The device refill round and the tier/filter path: spans, the profiler
# mirror, the slab-download counter, and nothing at all with obs=None.
# ---------------------------------------------------------------------------
def _device_serve(store, obs, n_slots=2):
    from repro.storage import make_tier_stack

    eng = NeedleTailEngine(store, tiers=make_tier_stack(1 << 20, 1 << 20))
    serve = ServeEngine(None, None, max_slots=n_slots, exemplar_device=True,
                        exemplar_policy=AdmissionPolicy(max_wave=n_slots), obs=obs)
    reqs = [serve.submit_exemplar_request(q.predicates, q.k) for q in _queries()]
    for _ in range(64):
        if all(r.done for r in reqs):
            break
        serve.step(eng, drain=True)
    assert all(r.done for r in reqs)
    return eng, reqs


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs enters and exits."""

    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def test_span_helper_and_profiler_mirror(monkeypatch):
    import repro.obs.trace as obs_trace
    from repro.obs import span

    assert span(None, "plan.round", site="device") is NULL_SPAN
    monkeypatch.setattr(obs_trace, "TraceAnnotation", _Annotations)
    monkeypatch.setattr(_Annotations, "log", [])
    clk = CountingClock()
    rec = TraceRecorder(clock=clk)
    with span(rec, "outer", a=1):
        with span(rec, "inner"):
            pass
    assert _Annotations.log == [("enter", "outer"), ("enter", "inner"),
                                ("exit", "inner"), ("exit", "outer")]
    assert [e["name"] for e in rec.to_events()] == ["inner", "outer"]
    assert clk.calls == 4  # still two clock reads per span
    off = TraceRecorder(clock=clk, enabled=False)
    assert span(off, "x") is NULL_SPAN


def test_device_serve_without_recorder_opens_no_span(store, monkeypatch):
    import repro.obs.trace as obs_trace

    opened = []
    monkeypatch.setattr(obs_trace._Span, "__enter__",
                        lambda self: opened.append(self.name) or self)
    monkeypatch.setattr(obs_trace, "TraceAnnotation", _Annotations)
    monkeypatch.setattr(_Annotations, "log", [])
    _device_serve(store, None)
    assert opened == [] and _Annotations.log == []
    clk = CountingClock()
    _device_serve(store, TraceRecorder(clock=clk, enabled=False))
    assert clk.calls == 0, "disabled recorder read the clock on the device path"
    assert opened == [] and _Annotations.log == []


def test_device_round_spans_nest_and_feed_the_report(store):
    from tools.trace_report import request_paths, wave_summary

    rec = TraceRecorder(clock=CountingClock(dt=0.0005))
    eng, reqs = _device_serve(store, rec)
    events = rec.to_events()
    spans = {e["id"]: e for e in events if e["kind"] == "span"}
    parent = {"plan.join": "plan.round", "plan.device_round": "plan.round",
              "plan.decode": "plan.round", "tier.fill": "wave.execute",
              "wave.filter": "wave.execute", "serve.exemplar_tick": "serve.step"}
    seen = set()
    for e in spans.values():
        if e["name"] in parent:
            seen.add(e["name"])
            assert spans[e["parent"]]["name"] == parent[e["name"]]
    assert seen == set(parent)
    rounds = [e for e in spans.values() if e["name"] == "plan.round"]
    assert all(e["attrs"]["site"] == "device" and e["attrs"]["choices"] for e in rounds)
    assert not [e for e in events if e["kind"] == "event" and e["name"] == "plan.round"]
    for e in spans.values():
        a = e.get("attrs", {})
        if e["name"] == "tier.fill":
            assert a["in_hbm"] + a["in_dram"] + a["from_store"] == a["union"] > 0
        elif e["name"] == "wave.filter":
            assert a["blocks"] > 0 and a["rows_kept"] >= 0
        elif e["name"] == "plan.device_round":
            assert a["nbytes"] > 0
    # the report counts the device path's choices from its spans
    summary = wave_summary(events)
    assert sum(summary["plan_choices"].values()) == sum(
        e["attrs"]["n_active"] for e in rounds)
    assert sorted(request_paths(events)) == sorted(r.rid for r in reqs)


def test_hbm_host_downloads_count_one_per_residency(store):
    from repro.storage import make_tier_stack

    stack = make_tier_stack(1 << 20, None)
    slab = stack.block_nbytes(store)
    stack.ensure(store, [1, 2, 3])
    c0 = stack.tier_counters()
    assert c0["hbm.host_downloads"] == 0 and "dram.host_downloads" not in c0
    stack.get_many(store, [1, 2])
    stack.get_many(store, [2, 3, 1])
    c = stack.tier_counters()
    assert c["hbm.host_downloads"] == 3
    assert c["hbm.host_download_bytes"] == 3 * slab
