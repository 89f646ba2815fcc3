"""Mixes that hold online aggregates (AVG) beside LIMIT requests: a whole run
of a mixed cell at a CPU test's size, the end-to-end reduction over both
kinds, the traffic split, and a pin on the ``nt71-browse`` arrivals."""
import hashlib
import json
import time
from collections import Counter

import numpy as np
import pytest

from chipbench import harness, traffic
from chipbench.tests.mixed import AGG_TEMPLATES, mixed_cell, mixed_mix
from chipbench.tests.tiny import tiny_cell, tiny_run
from chipbench.traffic import Arrival


def test_mixed_cell_serves_times_and_checks_every_aggregate(tmp_path, quick_drain, monkeypatch):
    import jax

    from repro.serving.engine import AggregateRequest, ServeEngine

    cell = mixed_cell(tmp_path)
    seen = {"submitted": set(), "answered": set()}
    submit, step = ServeEngine.submit_aggregate_request, ServeEngine.step

    def spy_submit(self, *a, **kw):
        req = submit(self, *a, **kw)
        seen["submitted"].add(req.rid)
        return req

    def spy_step(self, *a, **kw):
        out = step(self, *a, **kw)
        seen["answered"].update(r.rid for r in out["aggregate"])
        return out

    got = {}
    check = harness.check

    def spy_check(cell, sv, out, log=None):
        got["out"] = out
        got["checks"] = check(cell, sv, out, log)
        return got["checks"]

    monkeypatch.setattr(ServeEngine, "submit_aggregate_request", spy_submit)
    monkeypatch.setattr(ServeEngine, "step", spy_step)
    monkeypatch.setattr(harness, "check", spy_check)
    logs = []
    # every gather bucket the tiny table can ask for is warmed, as on the
    # chip, so an aggregate's fill of newly missed blocks compiles nothing
    res = harness.run(cell, 2**33 + 3, 3.0, False, jax.devices(), time.monotonic(),
                      log=logs.append, max_bucket=128, joins=2)
    assert res["correct"] is True, res["checks"]
    recs = got["out"].records
    kinds = Counter(r["arrival"].kind for r in recs)
    assert kinds[traffic.EXEMPLAR] > 0 and kinds[traffic.AGGREGATE] > 0
    assert res["attempted"] == len(recs) and res["failed"] == 0
    aggs = [r for r in recs if r["arrival"].kind == traffic.AGGREGATE]
    for r in aggs:
        req = r["req"]
        assert isinstance(req, AggregateRequest) and req.done
        assert req.rid in seen["submitted"] and req.rid in seen["answered"]
        assert r["done"] >= r["due"]  # timed from its due time
        assert req.error_slo == r["arrival"].error_slo and req.measure == r["arrival"].measure
    assert set(res["checks"]) == {"missing", "bad_rows", "short", "agg_over_slo",
                                  "agg_exhausted_off", "agg_outside_4se"}
    assert res["checks"]["agg_outside_4se"]["limit"] == 2
    assert res["checks"]["agg_over_slo"]["limit"] == 0
    assert res["checks"]["agg_exhausted_off"]["limit"] == 0
    assert any(line.startswith(f"aggregates_checked={len(aggs)} ") for line in logs), logs
    assert any("window_compiles=0 " in line for line in logs), logs


def test_browse_result_keeps_its_checks():
    res = tiny_run("nt71-browse", seconds=1.0)
    assert list(res["checks"]) == ["missing", "bad_rows", "short"]
    assert harness.limits(tiny_cell("nt71-browse")) == {"missing": 0, "bad_rows": 0,
                                                        "short": 0}


def test_an_aggregates_latency_counts_like_a_limit_requests():
    agg = Arrival(0.0, ((0, 1),), "and", 2000, kind=traffic.AGGREGATE, error_slo=0.5)
    ex = Arrival(0.0, ((0, 1),), "and", 100)
    rows = [(ex, 0.0, 0.1), (ex, 1.0, 1.1), (agg, 2.0, 2.9), (agg, 3.0, 3.9), (agg, 4.0, None)]
    recs = [{"arrival": a, "req": None, "due": due, "done": done} for a, due, done in rows]
    m = harness.end_to_end(harness.Outcome(recs, 0.0, 10.0, [], 0), [False] * 5)
    assert m["req_per_s"] == pytest.approx(4 / 10.0)
    assert m["p50_ms"] == pytest.approx(900.0)  # ranks: 100, 100, 900, 900, 6000
    assert m["p95_ms"] == pytest.approx(6000.0)  # the pending aggregate, at its age
    m = harness.end_to_end(harness.Outcome(recs, 0.0, 10.0, [], 0),
                           [False, False, True, False, False])
    assert m["req_per_s"] == pytest.approx(3 / 10.0)  # a failed aggregate misses


def _shape(arr):
    return Counter((a.kind, a.op, tuple(v for _, v in a.predicates), a.k, a.measure,
                    a.error_slo)
                   for a in arr)


@pytest.mark.parametrize("mixed", [False, True])
def test_split_gives_the_shares_and_shapes_the_mix_states(mixed):
    cfg = tiny_cell("nt71-browse").config
    mix = mixed_mix() if mixed else traffic.load_mix("nt71_browse")
    n = 255
    got = {s: traffic.requests(mix, cfg, n, np.random.default_rng(s)) for s in (3, 2**40 + 9)}
    assert _shape(got[3]) == _shape(got[2**40 + 9])  # the same work in every seed
    assert got[3] != got[2**40 + 9]  # in another order
    arr = got[3]
    assert len(arr) == n
    kinds = Counter(a.kind for a in arr)
    want_agg = round(0.2 * n) if mixed else 0
    assert kinds[traffic.AGGREGATE] == want_agg
    assert kinds[traffic.EXEMPLAR] == n - want_agg
    ex = [a for a in arr if a.kind == traffic.EXEMPLAR]
    assert {a.k for a in ex} == {100, 1000}
    assert all(a.error_slo is None and a.measure == 0 for a in ex)
    if mixed:
        agg = _shape(a for a in arr if a.kind == traffic.AGGREGATE)
        combos = {(traffic.AGGREGATE, op, vals, 2000, m, e)
                  for vals, op in AGG_TEMPLATES for m in (0, 1) for e in (0.3, 0.4, 0.5)}
        assert set(agg) == combos
        assert {agg[c] for c in combos} <= {want_agg // len(combos),
                                            want_agg // len(combos) + 1}
        for a in arr:
            if a.kind == traffic.AGGREGATE:  # values as templated, distinct attributes
                assert len({x for x, _ in a.predicates}) == len(a.predicates)
                vals = tuple(v for _, v in a.predicates)
                assert (vals, a.op) in AGG_TEMPLATES


# sha256 of the JSON of [[due_s.hex(), predicates, op, k], ...] of the
# nt71-browse arrivals over a 51 s window, as the generator made them before
# mixes could hold aggregates
BROWSE_DIGESTS = {
    7: (255, "d3274d89a4b6ac1b964dedbbb515a42201556b2e4689487a131459665fd24da7"),
    4400000037: (255, "3df53132f54038293b038df2970e68dd2a0283017fd02d8af76d3ca6bc64ef71"),
}


@pytest.mark.parametrize("seed", sorted(BROWSE_DIGESTS))
def test_browse_arrivals_are_pinned(seed):
    cell = harness.load_cell("nt71-browse")
    arr = traffic.arrivals(cell.mix, cell.config, 51.0, seed).due(float("inf"))
    assert all(a.kind == traffic.EXEMPLAR and a.error_slo is None for a in arr)
    rows = [[a.due_s.hex(), [list(p) for p in a.predicates], a.op, a.k] for a in arr]
    digest = hashlib.sha256(json.dumps(rows).encode()).hexdigest()
    assert (len(arr), digest) == BROWSE_DIGESTS[seed]
