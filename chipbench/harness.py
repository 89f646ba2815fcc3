"""One run of one cell: build the table from the seed, place it on the chip,
warm up, drive ``ServeEngine.step`` under the mix's arrivals for the window,
check every answer against the full scan, and reduce spans, counters and the
device trace to the cell's metrics.

The serving stack is the one ``chip_smoke.py`` serves:
``ServeEngine(cfg=None, params=None, max_slots=64, exemplar_device=True)``
over ``NeedleTailEngine(store, tiers=make_tier_stack(1 GiB, 4 GiB))``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import logging
import shutil
import tempfile
import time
from collections import deque
from pathlib import Path

import numpy as np

from chipbench import reference, roofline, stats, trace, traffic

ROOT = Path(__file__).resolve().parent
REPO = ROOT.parent
SLOTS = 64
HBM_TIER_BYTES = 1 << 30
DRAM_TIER_BYTES = 4 << 30
MAX_GATHER_BUCKET = 4096  # tier 0 holds 1 GiB / 335,872 B = 3,196 blocks
DRAIN_S = 60.0  # how long answers due in the window may come late
WARM_JOINS = 16  # joiners per tick warmed; drive() holds the admission queue to this
EXACT_CHECKS = ("missing", "bad_rows", "short")
AGG_EXACT_CHECKS = ("agg_over_slo", "agg_exhausted_off")  # agg_outside_4se: the mix's


# ------------------------------------------------------------ lookup by name
def load_spec(repo: Path = REPO) -> dict:
    return json.loads((repo / "BENCHMARK.json").read_text())


def _load_file(path: Path, what: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {what} at {path}")
    spec = importlib.util.spec_from_file_location(f"chipbench_{what}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(name: str, root: Path = ROOT) -> dict:
    path = root / "configs" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration {name!r} ({path})")
    return json.loads(path.read_text())


def table_module(cfg: dict, root: Path = ROOT):
    return _load_file(root / "tables" / f"{cfg['generator']}.py", "table")


def metric_module(name: str, root: Path = ROOT):
    """A per-layer metric's module: ``read(ctx)``, and optionally
    ``probe(served)``, a dict of numbers the harness takes at fixed moments
    of the window and hands back to ``read`` as ``ctx.probe``."""
    return _load_file(root / "metrics" / f"{name}.py", "metric")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    tables: object  # the config's table-generator module
    end_to_end: list  # BENCHMARK.json metric entries this cell reports
    per_layer: list
    root: Path = ROOT  # where its configuration, mix, arrivals and metrics live


def load_cell(workload: str, repo: Path = REPO, root: Path = ROOT) -> Cell:
    spec = load_spec(repo)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg = load_config(w["config"], root)
    mine = lambda m: workload in m.get("workloads", [workload])  # noqa: E731
    return Cell(
        name=workload, chips=int(w["chips"]), config=cfg,
        mix=traffic.load_mix(w["traffic"], root), tables=table_module(cfg, root),
        end_to_end=[m for m in spec["end_to_end"] if mine(m)],
        per_layer=[m for m in spec["per_layer"] if mine(m)], root=root,
    )


# ------------------------------------------------------------------ compiles
class CompileCounter:
    """Counts the XLA executables built (compiled, or loaded from the
    persistent cache) and the persistent-cache hits, via ``jax.monitoring``
    (process-wide; one per process).  While :meth:`naming` is on it keeps
    JAX's "Compiling ..." log lines, which name each function and its shapes,
    instead of printing them."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        self.names: list[str] = []
        self._saved = None

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        names = self.names

        class _Keep(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if msg.startswith("Compiling"):
                    names.append(msg[:400])

        self._handler = _Keep(logging.WARNING)

    def naming(self, on: bool) -> None:
        import jax

        log = logging.getLogger("jax")
        if on:
            self._saved = (log.handlers[:], log.propagate)
            log.handlers[:] = [self._handler]
            log.propagate = False
        elif self._saved is not None:
            log.handlers[:], log.propagate = self._saved
            self._saved = None
        jax.config.update("jax_log_compiles", on)


# --------------------------------------------------------------------- build
@dataclasses.dataclass
class Served:
    """The system under test, built from one seed."""

    table: object
    store: object
    engine: object
    server: object
    obs: object  # TraceRecorder or None


def build(cell: Cell, seed: int, traced: bool, timings: dict) -> Served:
    import jax

    from repro.core.density_map import build_density_maps
    from repro.core.engine import NeedleTailEngine
    from repro.data.block_store import BlockStore, blocked_layout
    from repro.obs import TraceRecorder
    from repro.serving.engine import ServeEngine
    from repro.storage import make_tier_stack

    cfg = cell.config
    rpb = int(cfg["records_per_block"])
    t = time.monotonic()
    table = cell.tables.make(cfg, seed)
    timings["generate"] = time.monotonic() - t
    t = time.monotonic()
    index = build_density_maps(table.dims, table.cards, rpb)
    jax.block_until_ready(index.densities)
    timings["index"] = time.monotonic() - t
    t = time.monotonic()
    dims, meas, valid = blocked_layout(table.dims, table.measures, rpb)
    store = BlockStore(dims, meas, valid, index, rpb, table.num_records)
    jax.block_until_ready((store.dims_dev, store.meas_dev))
    timings["upload"] = time.monotonic() - t
    # the configuration's guarantee is min(k, matches) rows: the engine's
    # default cap of 8 refill rounds leaves a selective request short on some
    # seeds, and every round reads a new block, so λ rounds reach them all
    engine = NeedleTailEngine(store, tiers=make_tier_stack(HBM_TIER_BYTES, DRAM_TIER_BYTES),
                              max_refills=store.num_blocks)
    obs = TraceRecorder(clock=time.monotonic, max_events=1 << 20) if traced else None
    server = ServeEngine(cfg=None, params=None, max_slots=SLOTS, exemplar_device=True,
                         obs=obs)
    return Served(table, store, engine, server, obs)


def warm(cell: Cell, sv: Served, seed: int, timings: dict,
         max_bucket: int = MAX_GATHER_BUCKET, joins: int = WARM_JOINS) -> None:
    """Compile what the window will run: the ``[64, λ]`` round program, the
    union gather at every power-of-two bucket tier 0 can take (with the
    per-bucket slicing the tier fill does), then a slice of the cell's own
    traffic from a separate seed stream, served to the end."""
    import jax
    import jax.numpy as jnp

    from repro.core.multi_query import DeviceWave

    t = time.monotonic()
    wave = DeviceWave(sv.engine, SLOTS)
    st = wave.state
    jax.block_until_ready(wave.round_fn(
        st.combined0, st.excl, st.th_mask, st.tp_win,
        jnp.asarray(wave.chosen), jnp.ones((wave.qb,), jnp.float32)))
    del wave, st
    b = 1
    while b <= min(max_bucket, sv.store.num_blocks):
        dd, dm, dv, _ = sv.store.gather_device(np.arange(b, dtype=np.int64))
        np.asarray(dd[0]), np.asarray(dm[0]), np.asarray(dv[0])
        b *= 2
    warm_joins(cell, sv, joins)
    warm_aggregates(cell, sv)
    timings["compile"] = time.monotonic() - t
    t = time.monotonic()
    warm_s = float(cell.mix.get("warmup_s", 5.0))
    out = drive(sv, traffic.arrivals(cell.mix, cell.config, warm_s, seed,
                                     stream=1, root=cell.root), warm_s, queue=joins)
    if out.unanswered:
        raise RuntimeError(f"warm-up left {out.unanswered} requests unanswered")
    timings["warmup"] = time.monotonic() - t


def warm_joins(cell: Cell, sv: Served, most: int) -> None:
    """Seat 1..``most`` joiners of each (op, term count) of the mix's
    exemplar templates in a throwaway device wave and plan one round: the
    per-join combine and scatter run op by op, one executable per joiner
    count, and this builds them before the window."""
    from repro.core.multi_query import BatchQuery, DeviceWave, new_query_state

    shapes = sorted({(t["op"], len(t["terms"])) for c in cell.mix["classes"]
                     if traffic.class_kind(c) == traffic.EXEMPLAR for t in c["templates"]})
    wave = DeviceWave(sv.engine, SLOTS)
    for op, g in shapes:
        preds = [(a, 0) for a in range(g)]
        for j in range(1, most + 1):
            for slot in range(j):
                wave.join(slot, new_query_state(BatchQuery(preds, 1, op)))
            wave.plan_round()
            for slot in range(j):
                wave.leave(slot)


def warm_aggregates(cell: Cell, sv: Served) -> None:
    """Serve one aggregate of each (op, term count, measure) of the mix's
    aggregate classes to its answer: the any-k plan of its chosen arm, the
    tier fills and the host fold, built before the window."""
    todo = {}
    for c in cell.mix["classes"]:
        if traffic.class_kind(c) != traffic.AGGREGATE:
            continue
        combos = len(c["templates"]) * len(c["k"]) * len(c["measure"]) * len(c["error_slo"])
        for a in traffic.class_requests(c, combos, cell.config):
            todo.setdefault((a.op, len(a.predicates), a.measure), a)
    reqs = [_submit(sv.server, a) for a in todo.values()]
    sv.server.run_continuous(sv.engine)
    if not all(r.done for r in reqs):
        raise RuntimeError("the aggregate warm-up left requests unanswered")


# --------------------------------------------------------------------- drive
@dataclasses.dataclass
class Outcome:
    records: list  # per arrival: {"arrival", "req", "due", "done", "late"}
    t0: float
    t_end: float  # the window's close (monotonic)
    steps: list  # (start, end, exemplar_active)
    unanswered: int  # due in the window, no answer DRAIN_S after the close


def _admission(server, kind: str):
    return (server.aggregate_admission if kind == traffic.AGGREGATE
            else server.exemplar_admission)


def _pools(server):
    """(exemplar requests waiting or seated, requests of either pool waiting
    or seated, slots of either pool seated)."""
    ex, agg = getattr(server, "_exemplar_loop", None), getattr(server, "_aggregate_loop", None)
    ex_busy = ex.sched.busy if ex is not None else 0
    agg_busy = agg.sched.busy if agg is not None else 0
    ex_act = ex_busy + server.exemplar_admission.pending
    return (ex_act, ex_act + agg_busy + server.aggregate_admission.pending,
            ex_busy + agg_busy)


def _submit(server, a: traffic.Arrival):
    preds = [tuple(p) for p in a.predicates]
    if a.kind == traffic.AGGREGATE:
        return server.submit_aggregate_request(preds, a.measure, a.k, op=a.op,
                                               error_slo=a.error_slo)
    return server.submit_exemplar_request(preds, a.k, a.op)


def drive(sv: Served, process, seconds: float, hook=None,
          queue: int = WARM_JOINS) -> Outcome:
    """Submit each arrival of ``process`` (``traffic.arrivals``) at its due
    time and tick ``ServeEngine.step`` whenever a pool holds work; close the
    window after ``seconds``, then keep ticking, without new arrivals, until
    every request due in the window is answered or ``DRAIN_S`` has passed.
    ``hook(rel_s)`` runs between steps inside the window, and with ``None``
    at its close (the profiler's stop, the probes).

    A due request waits on the client's side while ``queue`` requests of
    its kind wait for that pool's admission.  A tick seats all the queue
    holds, and the program builds programs for each number of exemplar
    joiners, of which the warm-up builds 1..``queue``: after a host stall of
    a few seconds the queue would otherwise seat more and compile inside the
    window.  The latency of a request that waited runs from its due time all
    the same."""
    server, engine = sv.server, sv.engine
    records: list = []
    by_rid: dict = {}
    steps: list = []
    # due, not yet submitted, per pool
    held = {traffic.EXEMPLAR: deque(), traffic.AGGREGATE: deque()}
    t0 = time.monotonic()
    window_open = True

    def arrive(arrivals) -> None:
        for a in arrivals:
            rec = {"arrival": a, "req": None, "due": t0 + a.due_s, "done": None,
                   "late": None}
            records.append(rec)
            held[a.kind].append(rec)
        release()

    def release() -> None:
        for kind, waiting in held.items():
            adm = _admission(server, kind)
            while waiting and adm.pending < queue:
                rec = waiting.popleft()
                req = _submit(server, rec["arrival"])
                rec["req"] = req
                rec["late"] = time.monotonic() - rec["due"]
                by_rid[req.rid] = rec

    def step(now: float) -> None:
        release()
        ex_act, _, _ = _pools(server)
        out = server.step(engine, now=now)
        t1 = time.monotonic()
        steps.append((now, t1, ex_act > 0))
        for req in out["exemplar"] + out["aggregate"]:
            rec = by_rid[req.rid]
            rec["done"] = t1
            if window_open:
                process.done(rec["arrival"], t1 - t0)

    while True:
        now = time.monotonic()
        if now - t0 >= seconds:
            break
        if hook is not None:
            hook(now - t0)
        arrive(process.due(now - t0))
        _, act, busy = _pools(server)
        if busy == 0:
            # no slot seated: sleep to the next arrival or admission deadline
            wake = [t0 + seconds]
            nxt = process.next_due()
            if nxt is not None:
                wake.append(t0 + nxt)
            for adm in (server.exemplar_admission, server.aggregate_admission):
                d = adm.next_deadline()
                if d is not None:
                    wake.append(d)
            pause = min(wake) - time.monotonic()
            if pause > 0:
                time.sleep(min(pause, 0.05))
                continue
            if act == 0:
                continue
        step(time.monotonic())
    t_end = time.monotonic()
    window_open = False
    if hook is not None:
        hook(None)
    # due in the window, not yet submitted at its close
    arrive([a for a in process.due(seconds) if a.due_s < seconds])
    drain_from = time.monotonic()  # the profiler's stop is not drain time
    while time.monotonic() - drain_from < DRAIN_S:
        if all(r["done"] is not None for r in records):
            break
        step(time.monotonic())
    unanswered = sum(1 for r in records if r["done"] is None)
    return Outcome(records, t0, t_end, steps, unanswered)


# --------------------------------------------------------------------- check
def check(cell: Cell, sv: Served, out: Outcome, log=None) -> tuple[dict, list[bool]]:
    """Compare every answer due in the window with the full scan.  Returns
    the numbers compared and, per record, whether it failed; ``log`` gets a
    line on the aggregates: answers by reason, and the spread of their
    distance from the truth in standard errors.

    A sound run may hold answers beyond 4 standard errors, up to the mix's
    limit, so such an answer is a failed record only in a run whose count
    exceeds that limit."""
    scan = reference.FullScan(sv.table)
    rpb = int(cell.config["records_per_block"])
    n = {"bad_rows": 0, "short": 0, "over_slo": 0, "exhausted_off": 0, "outside_4se": 0}
    reasons: dict = {}
    zs = []
    failed = []
    outside = []  # per record: beyond 4 SE and no exact check failed
    for r in out.records:
        a, req = r["arrival"], r["req"]
        outside.append(False)
        if r["done"] is None or req is None or req.result is None:
            failed.append(True)
            continue
        preds = [tuple(p) for p in a.predicates]
        if a.kind == traffic.AGGREGATE:
            c = reference.check_aggregate(req, preds, a.op, a.measure, a.error_slo, scan)
            reasons[req.reason] = reasons.get(req.reason, 0) + 1
            zs.append(c.pop("z"))
        else:
            c = reference.check_exemplar(req.result, preds, a.k, a.op, scan, rpb)
            c.pop("rows")
        for k, v in c.items():
            n[k] += v
        failed.append(any(v > 0 for k, v in c.items() if k != "outside_4se"))
        outside[-1] = not failed[-1] and c.get("outside_4se", 0) > 0
    checks = {"missing": out.unanswered, "bad_rows": n["bad_rows"], "short": n["short"]}
    if traffic.has_aggregates(cell.mix):
        checks.update(agg_over_slo=n["over_slo"], agg_exhausted_off=n["exhausted_off"],
                      agg_outside_4se=n["outside_4se"])
        if n["outside_4se"] > limits(cell)["agg_outside_4se"]:
            failed = [f or o for f, o in zip(failed, outside)]
        if log is not None and zs:
            log(f"aggregates_checked={len(zs)} by_reason={json.dumps(reasons, sort_keys=True)} "
                f"err_over_se p50={stats.percentile(zs, 50):.3f} "
                f"p90={stats.percentile(zs, 90):.3f} max={max(zs):.3f} "
                f"over_2={sum(z > 2 for z in zs)} over_3={sum(z > 3 for z in zs)}")
    return checks, failed


def limits(cell: Cell) -> dict:
    lim = {k: 0 for k in EXACT_CHECKS}
    if traffic.has_aggregates(cell.mix):
        lim.update({k: 0 for k in AGG_EXACT_CHECKS})
    lim.update(cell.mix.get("limits", {}))
    return lim


def correct(checks: dict, lim: dict) -> bool:
    return all(checks[k] <= lim[k] for k in checks)


# ------------------------------------------------------------------- metrics
def end_to_end(out: Outcome, failed: list[bool]) -> dict:
    """Tails over every request due in the window: an answer's latency runs
    from its due time to its return; a request unanswered at the close, or
    answered wrongly, counts at its age at the close (or its latency, if
    larger)."""
    window = out.t_end - out.t0
    lat = []
    completed = 0
    for r, bad in zip(out.records, failed):
        if r["done"] is not None and r["done"] <= out.t_end and not bad:
            completed += 1
            lat.append(r["done"] - r["due"])
        else:
            age = out.t_end - r["due"]
            if r["done"] is not None:
                age = max(age, r["done"] - r["due"])
            lat.append(age)
    m = {"req_per_s": completed / window}
    if lat:
        m["p50_ms"] = 1e3 * stats.percentile(lat, 50)
        m["p95_ms"] = 1e3 * stats.percentile(lat, 95)
    return m


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read: the recorder's spans and
    events, the harness's step log, the reduced device trace, the answers,
    the run's end-to-end readings, the shapes its byte functions need, the
    chip's peaks, and what its own ``probe(served)`` returned at each moment
    it was taken (``open`` and ``close`` of the window, ``trace_start`` and
    ``trace_stop`` of the profiler's)."""

    spans: dict  # name -> [(t0, t1, attrs)] inside the window
    events: dict  # name -> [attrs] inside the window
    steps: list  # (start, end, exemplar_active)
    trace: dict | None  # trace.reduce() over the profiler window
    answers: list  # (Arrival, request) answered inside the window
    end_to_end: dict  # this run's end_to_end() readings on the host clock
    shapes: dict  # qb, lam, rpb, dims, measures
    peaks: dict
    probe: dict = dataclasses.field(default_factory=dict)  # moment -> the reader's probe


def delta(probe: dict, first: str, last: str) -> dict:
    """A probe's numbers at ``last`` less those at ``first``."""
    if first not in probe or last not in probe:
        return {}
    return {k: probe[last][k] - probe[first].get(k, 0) for k in probe[last]}


def _recorded(obs, lo: float, hi: float) -> tuple[dict, dict]:
    spans: dict = {}
    events: dict = {}
    if obs is None:
        return spans, events
    for e in obs.to_events():
        if e["kind"] == "span" and e["t0"] >= lo and e["t1"] <= hi:
            spans.setdefault(e["name"], []).append((e["t0"], e["t1"], e.get("attrs", {})))
        elif e["kind"] == "event" and lo <= e["t"] <= hi:
            events.setdefault(e["name"], []).append(e.get("attrs", {}))
    return spans, events


class Profiler:
    """Wraps the whole window in ``jax.profiler``, with each step inside
    annotated so host and device share one clock, and calls
    ``take(moment)`` at both ends.  It starts before the window opens and
    stops at its close: starting or stopping the profiler holds the host
    for seconds, and inside the window that would stall the serving loop."""

    def __init__(self, take):
        self.take = take
        self.dir = None
        self.on = False
        self.bounds = None  # monotonic (start, stop)

    def start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        jax.profiler.start_trace(self.dir)
        self.on = True
        self.take("trace_start")
        self.bounds = [time.monotonic(), None]

    def stop(self) -> None:
        import jax

        if not self.on:
            return
        self.bounds[1] = time.monotonic()
        self.take("trace_stop")
        jax.profiler.stop_trace()
        self.on = False


def annotate_steps(sv: Served, prof: Profiler):
    """Wrap ``server.step`` so each call inside the profiled window carries a
    ``chipbench.step`` annotation and its monotonic start."""
    import jax

    inner = sv.server.step
    marks: list[float] = []

    def step(*a, **kw):
        if not prof.on:
            return inner(*a, **kw)
        marks.append(time.monotonic())
        with jax.profiler.TraceAnnotation("chipbench.step"):
            return inner(*a, **kw)

    sv.server.step = step
    return marks


def reduce_trace(prof: Profiler, marks: list[float], spans_all: dict) -> tuple[dict, dict]:
    """Device busy/idle and per-program time over the profiled window, and
    the breakdown: top device operations, and idle gaps named by the host
    span open in each."""
    pd = trace.load(prof.dir)
    steps = trace.host_events(pd, "chipbench.step")
    if not steps or not marks:
        raise RuntimeError("the profiler trace holds no annotated step")
    n = min(len(steps), len(marks))
    offset = float(np.median([steps[j][0] - marks[j] * 1e9 for j in range(n)]))
    lo = prof.bounds[0] * 1e9 + offset
    hi = prof.bounds[1] * 1e9 + offset
    red = trace.reduce(pd, (lo, hi))
    host = []
    for name, rows in spans_all.items():
        for t0, t1, _ in rows:
            host.append((t0 * 1e9 + offset, t1 * 1e9 + offset, name))

    def name_at(t: float) -> str:
        best = None
        for s, e, nm in host:
            if s <= t <= e and (best is None or s >= best[0]):
                best = (s, e, nm)
        return best[2] if best else "harness.wait"

    gaps = trace.idle_gaps(red["busy_intervals"], lo, hi, name_at)
    breakdown = {"device_ops": trace.top(red["ops"]), "idle_gaps": trace.top(gaps)}
    return red, breakdown


# ----------------------------------------------------------------------- run
def require_chip(chips: int):
    """Refuse every path that would hide the device: interpret-mode Pallas,
    a backend other than the TPU, fewer chips than the cell asks for."""
    import os

    flag = os.environ.get("PALLAS_INTERPRET")
    if flag is not None and flag not in ("0", "false", "False"):
        raise SystemExit(f"PALLAS_INTERPRET={flag} forces interpret mode; refusing")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devices[0].platform!r} devices")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} TPU chips, JAX found {len(devices)}")
    return devices[:chips]


def run(cell: Cell, seed: int, seconds: float, traced: bool, devices, t_start: float,
        log=print, max_bucket: int = MAX_GATHER_BUCKET, joins: int = WARM_JOINS) -> dict:
    """One run; returns the result line's object (``checks`` last)."""
    timings: dict = {}
    counter = CompileCounter()
    sv = build(cell, seed, traced, timings)
    warm(cell, sv, seed, timings, max_bucket, joins)
    setup_s = time.monotonic() - t_start
    log(f"setup {json.dumps({k: round(v, 3) for k, v in timings.items()})} "
        f"setup_s={setup_s:.3f} compiles={counter.compiles} cache_hits={counter.cache_hits}")
    process = traffic.arrivals(cell.mix, cell.config, seconds, seed, stream=0,
                               root=cell.root)
    mods = ({m["name"]: metric_module(m["name"], cell.root) for m in cell.per_layer}
            if traced else {})
    probes = {n: {} for n, mod in mods.items() if hasattr(mod, "probe")}

    def take(moment: str) -> None:
        for n in probes:
            probes[n][moment] = mods[n].probe(sv)

    take("open")
    prof = Profiler(take) if traced else None
    marks = annotate_steps(sv, prof) if traced else []
    c0 = counter.compiles
    at = {}

    def hook(rel):
        if rel is None:  # the window's close: probes and compiles stop here
            if prof is not None:
                prof.stop()
            take("close")
            at["compiles"] = counter.compiles - c0
            counter.naming(False)

    counter.naming(True)
    if prof is not None:
        prof.start()
    try:
        out = drive(sv, process, seconds, hook=hook, queue=joins)
    finally:
        counter.naming(False)
        if prof is not None:
            prof.stop()
    late = [r["late"] for r in out.records if r["late"] is not None]
    log(f"window_compiles={at['compiles']} window_lowerings={len(counter.names)} "
        f"submit_late_ms p50={1e3 * stats.percentile(late, 50) if late else 0:.3f} "
        f"max={1e3 * max(late, default=0):.3f}")
    for name in counter.names[:20]:
        log(f"  in-window: {name}")
    dev = devices[0]
    mem = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": mem.get("peak_bytes_in_use")}
    answers = [(r["arrival"], r["req"]) for r in out.records
               if r["done"] is not None and r["done"] <= out.t_end]
    ex = [req for a, req in answers if a.kind == traffic.EXEMPLAR]
    agg = [req for a, req in answers if a.kind == traffic.AGGREGATE]
    blocks = sum(int(req.result.blocks_fetched.size) for req in ex)
    log(f"answered_in_window={len(answers)} blocks={blocks} modeled_io_s="
        f"{sum(req.result.modeled_io_s for req in ex):.3f} "
        "(repro.core.cost_model's HDD model: a count, not chip time)"
        + (f" aggregates={len(agg)} agg_spent_io_s={sum(r.spent_io_s for r in agg):.3f}"
           if agg else ""))
    spans, events = _recorded(sv.obs, out.t0, out.t_end)
    # the program's state is freed before the reference runs
    table = sv.table
    sv.server = sv.engine = sv.store = None
    checks, failed = check(cell, Served(table, None, None, None, None), out, log=log)
    result = {"correct": None, "attempted": len(out.records), "failed": int(sum(failed)),
              "metrics": {}, "device": device}
    e2e = end_to_end(out, failed)
    if not traced:
        m = dict(e2e, setup_s=setup_s)
        for spec in cell.end_to_end:
            if spec["name"] in m:
                result["metrics"][spec["name"]] = {"value": m[spec["name"]], "unit": spec["unit"]}
    else:
        red, breakdown = reduce_trace(prof, marks, spans)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        rpb = int(cell.config["records_per_block"])
        shapes = {"qb": SLOTS, "lam": -(-table.num_records // rpb), "rpb": rpb,
                  "dims": int(table.dims.shape[1]), "measures": int(table.measures.shape[1])}
        ctx = Context(spans=spans, events=events,
                      steps=[s for s in out.steps if s[1] <= out.t_end],
                      trace=red, answers=answers, end_to_end=e2e, shapes=shapes,
                      peaks=roofline.peaks(dev.device_kind) if dev.platform == "tpu" else {})
        for spec in cell.per_layer:
            name = spec["name"]
            v = mods[name].read(dataclasses.replace(ctx, probe=probes.get(name, {})))
            if v is not None:
                result["metrics"][name] = {"value": v, "unit": spec["unit"]}
        result["breakdown"] = breakdown
        shutil.rmtree(prof.dir, ignore_errors=True)
    lim = limits(cell)
    result["correct"] = correct(checks, lim)
    result["checks"] = {k: {"value": checks[k], "limit": lim[k]} for k in checks}
    return result
