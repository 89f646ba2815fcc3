"""The aggregate checks catch a broken timed path, each fault by its own
count, on a tiny mixed cell whose measure 0 drifts with record position (so
the densest blocks are a biased sample of it); and the sound run passes
them all."""
import dataclasses

import pytest

from chipbench import control, harness, reference, traffic
from chipbench.tests.mixed import DRIFT_TABLE, mixed_cell, mixed_mix
from chipbench.tests.tiny import tiny_run

SEED = 2**37 + 11
AGG_CHECKS = ("agg_over_slo", "agg_exhausted_off", "agg_outside_4se")


def _run(cell, seconds: float = 3.0):
    return tiny_run(cell.name, seconds=seconds, seed=SEED, cell=cell)


def _alter_answers(monkeypatch, alter):
    """Alter each answered aggregate where the serving loop produces it."""
    from repro.serving.engine import ServeEngine

    real = ServeEngine.aggregate_tick

    def tick(self, engine, now=None, drain=False):
        done = real(self, engine, now=now, drain=drain)
        for req in done:
            alter(req)
        return done

    monkeypatch.setattr(ServeEngine, "aggregate_tick", tick)


def _only_failing(res, name):
    checks = res["checks"]
    assert res["correct"] is False
    assert checks[name]["value"] > checks[name]["limit"], checks
    assert all(checks[k]["value"] <= checks[k]["limit"]
               for k in checks if k != name), checks


@pytest.mark.parametrize("slo", [(0.3, 0.4, 0.5), (None,)])
def test_sound_run_passes_every_aggregate_check(tmp_path, quick_drain, slo):
    res = _run(mixed_cell(tmp_path, mixed_mix(error_slo=slo), table=DRIFT_TABLE))
    assert res["correct"] is True, res["checks"]
    assert set(AGG_CHECKS) <= set(res["checks"])


def test_control_fails_outside_4se(tmp_path, quick_drain):
    cell = mixed_cell(tmp_path, mixed_mix(rate=10.0), table=DRIFT_TABLE)
    sv = harness.build(cell, SEED, False, {})
    harness.warm(cell, sv, SEED, {}, max_bucket=2, joins=2)
    out = harness.drive(sv, traffic.arrivals(cell.mix, cell.config, 4.0, SEED, root=cell.root),
                        4.0, queue=2)
    lim = harness.limits(cell)
    program, _ = harness.check(cell, sv, out)
    assert harness.correct(program, lim), program
    # LIMIT answers keep their own control; the aggregates' alone fail here
    records = [r if r["arrival"].kind == traffic.EXEMPLAR
               else control.apply([r], sv.table, int(cell.config["records_per_block"]))[0]
               for r in out.records]
    checks, failed = harness.check(cell, sv, dataclasses.replace(out, records=records))
    assert checks["agg_outside_4se"] > lim["agg_outside_4se"], checks
    assert all(checks[k] <= lim[k] for k in checks if k != "agg_outside_4se"), checks
    assert any(failed)


def test_answers_beyond_4se_are_failed_records_only_over_the_limit(tmp_path, quick_drain,
                                                                   monkeypatch):
    got = {}
    check = harness.check

    def spy_check(cell, sv, out, log=None):
        got.update(sv=sv, out=out)
        return check(cell, sv, out, log)

    monkeypatch.setattr(harness, "check", spy_check)
    cell = mixed_cell(tmp_path, table=DRIFT_TABLE)
    assert _run(cell)["correct"] is True
    real = reference.check_aggregate
    monkeypatch.setattr(reference, "check_aggregate",
                        lambda *a, **kw: dict(real(*a, **kw), outside_4se=1))
    is_agg = [r["arrival"].kind == traffic.AGGREGATE for r in got["out"].records]
    n_agg = sum(is_agg)
    assert n_agg > 0
    cell.mix["limits"]["agg_outside_4se"] = n_agg  # a count within its limit
    checks, failed = check(cell, got["sv"], got["out"])
    assert checks["agg_outside_4se"] == n_agg and not any(failed), checks
    cell.mix["limits"]["agg_outside_4se"] = n_agg - 1  # and one over it
    checks, failed = check(cell, got["sv"], got["out"])
    assert failed == is_agg, checks


def test_ci_answer_wider_than_its_slo_fails_over_slo(tmp_path, quick_drain, monkeypatch):
    def widen(req):  # the standard error only grows: no mean moves off
        if req.reason == "ci":
            se = 2.0 * req.error_slo / reference.Z95
            req.result = dataclasses.replace(req.result, var_mean=se * se)

    _alter_answers(monkeypatch, widen)
    _only_failing(_run(mixed_cell(tmp_path, table=DRIFT_TABLE)), "agg_over_slo")


def test_early_answer_labelled_budget_fails_over_slo(tmp_path, quick_drain, monkeypatch):
    relabelled = []

    def stop_early(req):  # a "ci" answer handed back after its first round
        if req.reason == "ci" and req.rounds > 1:
            req.result, req.rounds, req.reason = req.stream[0], 1, "budget"
            relabelled.append(req.rid)

    _alter_answers(monkeypatch, stop_early)
    _only_failing(_run(mixed_cell(tmp_path, table=DRIFT_TABLE)), "agg_over_slo")
    assert relabelled


def test_exhausted_answer_shifted_fails_exhausted_off(tmp_path, quick_drain, monkeypatch):
    def shift(req):
        if req.reason == "exhausted":
            req.result = dataclasses.replace(req.result, mean=req.result.mean * (1 + 1e-3))

    _alter_answers(monkeypatch, shift)
    cell = mixed_cell(tmp_path, mixed_mix(error_slo=(None,)), table=DRIFT_TABLE)
    _only_failing(_run(cell), "agg_exhausted_off")


def test_aggregate_never_answered_fails_missing(tmp_path, quick_drain, monkeypatch):
    from repro.serving.engine import ServeEngine

    real_warm = harness.warm

    def warm_then_break(*a, **kw):
        real_warm(*a, **kw)
        real = ServeEngine.step

        def lose(self, engine=None, now=None, drain=False):
            return dict(real(self, engine, now=now, drain=drain), aggregate=[])

        monkeypatch.setattr(ServeEngine, "step", lose)

    monkeypatch.setattr(harness, "warm", warm_then_break)
    got = {}
    check = harness.check

    def spy_check(cell, sv, out, log=None):
        got["out"] = out
        return check(cell, sv, out, log)

    monkeypatch.setattr(harness, "check", spy_check)
    res = _run(mixed_cell(tmp_path, table=DRIFT_TABLE))
    _only_failing(res, "missing")
    n_agg = sum(r["arrival"].kind == traffic.AGGREGATE for r in got["out"].records)
    assert res["checks"]["missing"]["value"] == n_agg > 0


def test_mix_with_aggregates_and_no_4se_limit_is_refused(tmp_path):
    with pytest.raises(ValueError, match="agg_outside_4se"):
        mixed_cell(tmp_path, mixed_mix(outside_4se=None))
