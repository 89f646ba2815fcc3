"""The control: the plain reference put in the program's place, breaking one
guarantee the configuration states, to show that the comparison which
decides ``correct`` fails it.

Each LIMIT answer becomes every valid row of the blocks the program
fetched, with no row filter (the step a later PR that drops the host row
filter would take).  That breaks "every returned row satisfies the
predicate".  Each aggregate answer becomes the mean of the any-k chosen arm
alone, with that sample's standard error: the densest blocks, in order,
until they hold ``k`` matching rows, which is the biased sample that the
paper's §5 debiasing corrects.  Where the measure follows the layout, that
breaks "the mean lies within 4 standard errors of the truth".

    python3 chipbench/control.py --workload nt71-browse --seeds 11 12 13 --seconds 10

runs, for each seed, the cell as a benchmark run does (at its own size and
load, a short window), then checks the program's answers and the control's
against the full scan and prints both sets of numbers.  The benchmark's own
runs never run it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

_REPO = Path(__file__).resolve().parents[1]
for _p in (str(_REPO / "src"), str(_REPO)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def unfiltered(res, table, rpb: int):
    """Every valid row of the blocks ``res`` fetched, in block order."""
    blocks = np.unique(np.asarray(res.blocks_fetched, np.int64))
    rows = np.concatenate([np.arange(b * rpb, min((b + 1) * rpb, table.num_records))
                           for b in blocks]) if blocks.size else np.zeros(0, np.int64)
    return dataclasses.replace(
        res, record_block=rows // rpb, record_row=rows % rpb,
        measures=table.measures[rows])


def chosen_arm_only(req, arrival, scan, rpb: int):
    """The mean of ``arrival``'s measure over the matching rows of the
    densest blocks, taken until they hold ``arrival.k`` of them, with the
    standard error of that sample as if it were a simple random one.  The
    answer says it spent its whole round budget (reason ``"budget"``), so
    the SLO check passes it by and only its distance from the truth is
    judged."""
    table = scan.table
    mask = scan.mask([tuple(p) for p in arrival.predicates], arrival.op)
    lam = -(-table.num_records // rpb)
    pad = np.zeros(lam * rpb, bool)
    pad[:mask.size] = mask
    counts = pad.reshape(lam, rpb).sum(axis=1)
    order = np.argsort(-counts, kind="stable")
    take = order[: int(np.searchsorted(np.cumsum(counts[order]), arrival.k)) + 1]
    rows = np.concatenate([np.arange(b * rpb, min((b + 1) * rpb, table.num_records))
                           for b in np.sort(take)])
    vals = table.measures[rows[mask[rows]], arrival.measure].astype(np.float64)
    n = vals.size
    mean = float(vals.mean()) if n else 0.0
    var = float(vals.var(ddof=1)) / n if n > 1 else 0.0
    est = dataclasses.replace(req.result, mean=mean, var_mean=var, num_samples=n)
    return dataclasses.replace(req, result=est, reason="budget", rounds=req.max_rounds)


def apply(records: list, table, rpb: int) -> list:
    """The window's records with each answer replaced by the control's."""
    from chipbench.reference import FullScan
    from chipbench.traffic import AGGREGATE

    scan = FullScan(table)
    out = []
    for r in records:
        req = r["req"]
        r = dict(r)
        if req is not None and req.result is not None:
            if r["arrival"].kind == AGGREGATE:
                r["req"] = chosen_arm_only(req, r["arrival"], scan, rpb)
            else:
                r["req"] = dataclasses.replace(req, result=unfiltered(req.result, table, rpb))
        out.append(r)
    return out


def _log(seed: int, side: str, msg: str) -> None:
    print(f"seed {seed} {side}: {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from chipbench import harness, traffic

    cell = harness.load_cell(args.workload)
    harness.require_chip(cell.chips)
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    rpb = int(cell.config["records_per_block"])
    lim = harness.limits(cell)
    for seed in args.seeds:
        t = time.monotonic()
        sv = harness.build(cell, seed, False, {})
        harness.warm(cell, sv, seed, {})
        process = traffic.arrivals(cell.mix, cell.config, args.seconds, seed, root=cell.root)
        out = harness.drive(sv, process, args.seconds)
        table = sv.table
        sv.server = sv.engine = sv.store = None
        served = harness.Served(table, None, None, None, None)
        program, _ = harness.check(cell, served, out, log=lambda s: _log(seed, "program", s))
        ctl_out = dataclasses.replace(out, records=apply(out.records, table, rpb))
        control, _ = harness.check(cell, served, ctl_out, log=lambda s: _log(seed, "control", s))
        print("control " + json.dumps({
            "workload": cell.name, "seed": seed, "answers": len(out.records),
            "program": program, "program_correct": harness.correct(program, lim),
            "control": control, "control_correct": harness.correct(control, lim),
            "seconds": round(time.monotonic() - t, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
