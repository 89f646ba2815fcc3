"""``chip_smoke.py`` on the CPU at a small size.

The script refuses to run off the chip, so these tests drive its phases
directly: the serving phase and its full-scan checks pass on honest answers,
and each check rejects an answer tampered in the way it exists to catch.
"""
import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.engine import NeedleTailEngine

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    return chip_smoke


@pytest.fixture(scope="module")
def small(smoke):
    return smoke.build_store(seed=0, num_records=60_000, records_per_block=512,
                             timings={})


def test_serve_and_check_passes_at_small_size(smoke, small):
    table, store = small
    summary = smoke.serve_and_check(store, table, seed=0)
    assert summary["exemplar"] == 36 and summary["aggregates"] == 4
    assert summary["identical_to_any_k"] == 12
    assert summary["hbm_admissions"] > 0  # tier-0 fills ran
    assert len(summary["aggregate_checks"]) == 4


def test_request_mix_spans_ops_widths_and_k(smoke):
    exemplar, aggregates = smoke.make_requests(3, 8)
    assert {op for _, _, op in exemplar} == {"and", "or"}
    assert {len(p) for p, _, _ in exemplar} == {1, 2, 3}
    assert min(k for _, k, _ in exemplar) == 100
    assert max(k for _, k, _ in exemplar) == 100_000
    assert exemplar == smoke.make_requests(3, 8)[0]  # seeded
    assert len(aggregates) == 4


@pytest.mark.parametrize("tamper", ["duplicate", "wrong_row", "short", "measures"])
def test_check_exemplar_rejects_tampered_answers(smoke, small, tamper):
    table, store = small
    scan = smoke.FullScan(table)
    preds, k, op = [(0, 1), (1, 1)], 300, "or"
    res = NeedleTailEngine(store).any_k(preds, k, op)
    rpb = store.records_per_block
    smoke.check_exemplar(res, preds, k, op, scan, rpb)
    blocks, rows, meas = res.record_block.copy(), res.record_row.copy(), res.measures.copy()
    if tamper == "duplicate":
        blocks[1], rows[1] = blocks[0], rows[0]
        meas[1] = meas[0]
    elif tamper == "wrong_row":
        m = scan.mask(preds, op)
        bad = int(np.flatnonzero(~m)[0])
        blocks[0], rows[0] = bad // rpb, bad % rpb
        meas[0] = table.measures[bad]
    elif tamper == "short":
        blocks, rows, meas = blocks[: k // 2], rows[: k // 2], meas[: k // 2]
    else:
        meas[0, 0] += 1.0
    bad_res = dataclasses.replace(res, record_block=blocks, record_row=rows,
                                  measures=meas)
    with pytest.raises(AssertionError):
        smoke.check_exemplar(bad_res, preds, k, op, scan, rpb)


def test_check_aggregate_rejects_an_estimate_outside_4_se(smoke, small):
    from repro.core.estimators import Estimate

    table, _ = small
    scan = smoke.FullScan(table)
    preds = [(2, 1)]
    truth = float(np.mean(table.measures[scan.mask(preds, "and"), 0], dtype=np.float64))

    class Req:
        predicates, op, measure, reason, rounds = preds, "and", 0, "ci", 1

    ok, off = Req(), Req()
    ok.result = Estimate(0.0, truth + 0.3, 0.0, 0.01, 100)  # 3 SE out
    off.result = Estimate(0.0, truth + 0.5, 0.0, 0.01, 100)  # 5 SE out
    smoke.check_aggregate(ok, scan)
    with pytest.raises(AssertionError):
        smoke.check_aggregate(off, scan)


def test_refuses_to_run_off_the_chip(smoke, monkeypatch):
    monkeypatch.delenv("PALLAS_INTERPRET", raising=False)
    with pytest.raises(SystemExit, match="no TPU"):
        smoke._require_chip(1)
    monkeypatch.setenv("PALLAS_INTERPRET", "1")
    with pytest.raises(SystemExit, match="interpret"):
        smoke._require_chip(1)
