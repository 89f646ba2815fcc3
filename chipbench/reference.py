"""The plain reference: a numpy full scan of the generated table, and the
comparison of every served answer against it.

Copied from ``chip_smoke.py`` (``FullScan``, ``check_exemplar``,
``check_aggregate``) and turned from assertions into counts, so a run can
print each compared number beside its limit.  Nothing here reads blocks,
density maps, plans or anything else the program built: only the table the
seed generated and the answers the timed path returned.

An aggregate's truth is the float64 mean of its measure over every matching
row.  Its answer is held to three numbers, for these reasons:

* ``agg_over_slo`` (exact): an answer to a request with an error SLO has a
  95% half-width, ``Z95`` standard errors, at or under that SLO, whatever
  reason it gives, unless it read its whole round budget (``rounds`` at
  ``max_rounds``) or is a ratio estimate of its whole design (reason
  ``"exhausted"``, which the next check holds to the exact mean); and no
  answer has a mean or a standard error that is not finite.
* ``agg_exhausted_off`` (exact): a ratio-estimator answer that folded every
  block of its design (reason ``"exhausted"``, π = 1) is the exact mean.  The
  1e-6 relative room absorbs the float32 block sums, as ``chip_smoke`` does.
* ``agg_outside_4se`` (statistical): any other answer's mean lies within 4
  standard errors of the truth, plus the same 1e-6 relative room (a design
  that read every matching block reports SE 0).  A sound estimator exceeds
  4 SE now and then: its SE rests on as few as 8 random-arm blocks, and
  Student's t with about 7 degrees of freedom lies beyond 4 about once in
  200.  So this count has a limit of its own, which each mix states from
  that sound rate (``chipbench.traffic`` gives the rule).  An answer beyond 4
  SE is a failed record only where the run's count exceeds the limit.
"""
from __future__ import annotations

import numpy as np

AND = "and"
Z95 = 1.959963984540054  # the standard normal's 97.5% quantile
EXACT_REL = 1e-6  # float32 block sums, relative to the mean


class FullScan:
    """Full-scan answers over the table, computed on demand and cached."""

    def __init__(self, table):
        self.table = table
        self._eq: dict = {}
        self._groups: dict = {}
        self._means: dict = {}

    def rows_match(self, idx: np.ndarray, preds, op: str) -> np.ndarray:
        """Whether each row of ``idx`` satisfies the predicate."""
        ms = [self.table.dims[idx, a] == v for a, v in preds]
        return np.logical_and.reduce(ms) if op == AND else np.logical_or.reduce(ms)

    def mask(self, preds, op: str) -> np.ndarray:
        ms = []
        for a, v in preds:
            if (a, v) not in self._eq:
                self._eq[(a, v)] = self.table.dims[:, a] == v
            ms.append(self._eq[(a, v)])
        return np.logical_and.reduce(ms) if op == AND else np.logical_or.reduce(ms)

    def mean(self, preds, op: str, measure: int) -> float:
        """The float64 mean of ``measure`` over the matching rows (0 when
        none match)."""
        key = (tuple(preds), op, measure)
        if key not in self._means:
            m = self.mask(preds, op)
            n = int(m.sum())
            total = np.sum(self.table.measures[:, measure], where=m, dtype=np.float64)
            self._means[key] = float(total / n) if n else 0.0
        return self._means[key]

    def count(self, preds, op: str) -> int:
        if op != AND:
            return int(self.mask(preds, op).sum())
        attrs = tuple(a for a, _ in preds)
        cards = [int(self.table.cards[a]) for a in attrs]
        if attrs not in self._groups:
            # one group-by pass per attribute set: a run's many AND counts
            # over the same attributes cost one scan
            code = np.zeros(self.table.num_records, np.int64)
            for a, c in zip(attrs, cards):
                code *= c
                code += self.table.dims[:, a]
            self._groups[attrs] = np.bincount(code, minlength=int(np.prod(cards)))
        cell = 0
        for (_, v), c in zip(preds, cards):
            cell = cell * c + int(v)
        return int(self._groups[attrs][cell])


def check_exemplar(res, preds, k: int, op: str, scan: FullScan, rpb: int) -> dict:
    """``bad_rows``: returned rows outside the table, repeated, failing the
    predicate, or with measures other than the table's; ``short``: 1 when
    fewer than min(k, matches) rows came back."""
    idx = res.record_block.astype(np.int64) * rpb + res.record_row.astype(np.int64)
    n = scan.table.num_records
    inside = (idx >= 0) & (idx < n)
    bad = int((~inside).sum())
    idx_in = idx[inside]
    bad += int(idx_in.size - np.unique(idx_in).size)
    bad += int((~scan.rows_match(idx_in, preds, op)).sum())
    meas = np.asarray(res.measures)
    if meas.shape != (idx.size, scan.table.measures.shape[1]):
        bad += int(idx.size)
    else:
        bad += int((~np.all(meas[inside] == scan.table.measures[idx_in], axis=1)).sum())
    short = 0
    if idx.size < k and idx.size < scan.count(preds, op):
        short = 1
    return {"bad_rows": bad, "short": short, "rows": int(idx.size)}


def check_aggregate(req, preds, op: str, measure: int, error_slo, scan: FullScan) -> dict:
    """Counts, 0 or 1 each, of an answered aggregate (module docstring):
    ``over_slo``, ``exhausted_off``, ``outside_4se``; and ``z``, its distance
    from the truth beyond the rounding room, in standard errors."""
    est = req.result
    truth = scan.mean(preds, op, measure)
    mean, se = float(est.mean), float(est.se_mean)
    err = abs(mean - truth)
    beyond = max(err - EXACT_REL * abs(truth), 0.0)  # outside the rounding room
    out = {"over_slo": 0, "exhausted_off": 0, "outside_4se": 0,
           "z": beyond / se if se > 0 else (0.0 if beyond == 0 else float("inf"))}
    if not (np.isfinite(mean) and np.isfinite(se)):
        out["over_slo"] = 1
        return out
    exact = req.reason == "exhausted" and req.estimator == "ratio"
    read_all = exact or req.rounds >= req.max_rounds
    if error_slo is not None and not read_all and Z95 * se > error_slo:
        out["over_slo"] = 1
    if exact:
        out["exhausted_off"] = int(not err <= EXACT_REL * abs(truth))
    else:
        out["outside_4se"] = int(not err <= 4.0 * se + EXACT_REL * abs(truth))
    return out
