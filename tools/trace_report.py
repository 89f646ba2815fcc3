#!/usr/bin/env python3
"""Render a serving trace (repro.obs JSONL export) as a human report.

Reads ONE artifact — the ``TraceRecorder.export_jsonl`` file — and needs no
live engine state: the event stream carries the whole request lifecycle
(queue wait → admission launch reason → per-round plan choice and predicted
io_time → fetch outcomes with predicted-vs-observed store io → device
transfers → completion), so the report reconstructs per-request critical
paths and per-wave summaries from the file alone.

Usage::

    python tools/trace_report.py TRACE.jsonl [--requests N]

Library surface (used by tests and the obs bench):

* :func:`load_events` — parse the JSONL.
* :func:`span_index` — spans by id (events reference their parent span).
* :func:`request_paths` — per-request critical path: submit/launch/done
  times, queue wait, launch reason, the tick spans the request rode in, and
  ``coverage`` (the fraction of its wall latency the trace accounts for).
* :func:`wave_summary` — per-span-name duration stats + plan/fetch rollups.
* :func:`render` — the text report.
"""
from __future__ import annotations

import argparse
import json
import math
from collections import defaultdict

TICK_SPANS = ("serve.exemplar_tick", "serve.aggregate_tick", "serve.lm_tick")


def load_events(path: str) -> list[dict]:
    """Parse a TraceRecorder JSONL export (one event per line)."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def span_index(events: list[dict]) -> dict[int, dict]:
    """Spans by id.  Point events carry ``parent`` span ids; spans carry
    their own ``parent`` too, so this is the whole tree."""
    return {e["id"]: e for e in events if e["kind"] == "span"}


def _attrs(e: dict) -> dict:
    return e.get("attrs", {})


def _quantile(vals: list[float], q: float) -> float:
    if not vals:
        return 0.0
    vs = sorted(vals)
    return vs[max(0, min(len(vs) - 1, math.ceil(q * len(vs)) - 1))]


def _merge_overlap(intervals: list[tuple[float, float]],
                   lo: float, hi: float) -> float:
    """Total length of the union of `intervals` clipped to [lo, hi]."""
    clipped = sorted(
        (max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi
    )
    total, end = 0.0, lo
    for a, b in clipped:
        a = max(a, end)
        if b > a:
            total += b - a
            end = b
    return total


def request_paths(events: list[dict]) -> dict[int, dict]:
    """Reconstruct each request's critical path from the stream alone.

    Returns ``{rid: {kind, submit_t, launch_t, done_t, wait_s, reason,
    ticks, busy_s, wall_s, coverage}}``.  ``coverage`` is the fraction of
    the request's wall latency ([submit, done]) accounted for by its queue
    wait plus the union of serving-tick spans overlapping its seated window
    — the "does the span tree sum to the wall latency" number the obs bench
    gates on.  Requests still in flight at export (no ``request.done``) are
    omitted.
    """
    reqs: dict[int, dict] = {}
    tick_spans: list[tuple[float, float]] = []
    for e in events:
        if e["kind"] == "span" and e["name"] in TICK_SPANS:
            tick_spans.append((e["t0"], e["t1"]))
        if e["kind"] != "event":
            continue
        a = _attrs(e)
        if e["name"] == "request.submit":
            reqs[a["rid"]] = {"kind": a.get("kind"), "submit_t": e["t"]}
        elif e["name"] == "admission.launch":
            for rid, wait in zip(a.get("rids", []), a.get("waits_s", [])):
                r = reqs.get(rid)
                if r is not None and "launch_t" not in r:
                    r["launch_t"] = e["t"]
                    r["wait_s"] = wait
                    r["reason"] = a.get("reason")
        elif e["name"] == "request.done":
            r = reqs.get(a["rid"])
            if r is not None:
                r["done_t"] = e["t"]
                r["rounds"] = a.get("rounds")
    out: dict[int, dict] = {}
    for rid, r in reqs.items():
        if "done_t" not in r:
            continue  # still in flight at export
        sub, done = r["submit_t"], r["done_t"]
        launch = r.get("launch_t", sub)
        wall = done - sub
        r["ticks"] = sum(1 for a, b in tick_spans if b > launch and a < done)
        busy = (launch - sub) + _merge_overlap(tick_spans, launch, done)
        r["wall_s"] = wall
        r["busy_s"] = busy
        r["coverage"] = (busy / wall) if wall > 0 else 1.0
        r.setdefault("wait_s", launch - sub)
        r.setdefault("reason", None)
        out[rid] = r
    return out


def wave_summary(events: list[dict]) -> dict:
    """Per-span-name duration stats plus plan/fetch rollups."""
    durs: dict[str, list[float]] = defaultdict(list)
    choices: dict[str, int] = defaultdict(int)
    reasons: dict[str, int] = defaultdict(int)
    fetch = {"n_blocks": 0, "predicted_io_s": 0.0, "observed_io_s": 0.0}
    transfers = 0
    for e in events:
        a = _attrs(e)
        if e["kind"] == "span":
            durs[e["name"]].append(e["t1"] - e["t0"])
            if e["name"] == "plan.round":
                for algo, n in (a.get("choices") or {}).items():
                    choices[algo] += n
        elif e["name"] == "admission.launch":
            reasons[a.get("reason", "?")] += 1
        elif e["name"] == "fetch.store":
            fetch["n_blocks"] += a.get("n", 0)
            fetch["predicted_io_s"] += a.get("predicted_io_s", 0.0)
            fetch["observed_io_s"] += a.get("observed_io_s", 0.0)
        elif e["name"] == "device.transfer":
            transfers += 1
    spans = {
        name: {
            "count": len(vs),
            "total_s": sum(vs),
            "p50_s": _quantile(vs, 0.50),
            "p99_s": _quantile(vs, 0.99),
        }
        for name, vs in sorted(durs.items())
    }
    return {
        "spans": spans,
        "plan_choices": dict(choices),
        "launch_reasons": dict(reasons),
        "store_fetch": fetch,
        "device_transfers": transfers,
    }


def render(events: list[dict], max_requests: int = 20) -> str:
    """The text report: per-request critical paths + per-wave summary."""
    paths = request_paths(events)
    summary = wave_summary(events)
    lines = [f"trace: {len(events)} events, {len(paths)} completed requests"]
    lines.append("")
    lines.append("requests (critical path):")
    lines.append(
        "  rid  kind       wall_ms  wait_ms  ticks  coverage  launch_reason"
    )
    for rid in sorted(paths)[:max_requests]:
        r = paths[rid]
        lines.append(
            f"  {rid:<4} {str(r['kind']):<10}"
            f" {1e3 * r['wall_s']:>7.2f}  {1e3 * r['wait_s']:>7.2f}"
            f"  {r['ticks']:>5}  {r['coverage']:>8.2%}  {r['reason']}"
        )
    if len(paths) > max_requests:
        lines.append(f"  ... {len(paths) - max_requests} more")
    lines.append("")
    lines.append("spans:")
    for name, s in summary["spans"].items():
        lines.append(
            f"  {name:<22} n={s['count']:<5} total={1e3 * s['total_s']:.2f}ms"
            f" p50={1e3 * s['p50_s']:.3f}ms p99={1e3 * s['p99_s']:.3f}ms"
        )
    lines.append("")
    lines.append(f"plan choices:   {summary['plan_choices']}")
    lines.append(f"launch reasons: {summary['launch_reasons']}")
    f = summary["store_fetch"]
    lines.append(
        f"store fetch:    {f['n_blocks']} blocks,"
        f" predicted {f['predicted_io_s']:.4f}s"
        f" observed {f['observed_io_s']:.4f}s"
    )
    lines.append(f"device transfers: {summary['device_transfers']}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="TraceRecorder JSONL export")
    ap.add_argument("--requests", type=int, default=20,
                    help="max per-request rows to print")
    args = ap.parse_args(argv)
    print(render(load_events(args.trace), max_requests=args.requests))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
