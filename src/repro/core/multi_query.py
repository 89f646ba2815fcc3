"""Batched multi-query any-k evaluation with shared-fetch scheduling.

The paper serves one LIMIT query at a time; production traffic arrives as waves
of small-k queries over the same hot blocks (BlinkDB's shared-I/O observation).
This module evaluates Q concurrent ``(predicates, k)`` requests as one unit:

1. **One combine pass** — all Q combined-density vectors are produced together:
   legacy pair-predicates pack into a ``[Q, γ_max]`` row matrix and go through
   the batched ⊕-combine (``combine_densities_batch_np`` on the host engine,
   the :func:`repro.kernels.density_combine.density_combine_batch` Pallas
   kernel on device); richer :class:`~repro.core.predicates.Predicate` trees
   fall back to their own density compiler.
2. **One vectorized plan** — all Q THRESHOLD / TWO-PRONG selections run in a
   single vmapped call instead of Q sequential jit dispatches: THRESHOLD
   shares one density sort per *unique* combined row
   (``threshold_sort_batch`` + per-query ``threshold_cut``), TWO-PRONG runs
   ``two_prong_select_batch`` over the unique (row, need) pairs.  When a mesh
   is attached (``run_batch(..., planner=DistributedAnyK)``, or
   :meth:`NeedleTailEngine.attach_mesh`), the plan wave instead runs as ONE
   ``shard_map`` collective over the λ-sharded density maps
   (:func:`repro.core.sharded.sharded_threshold_batch` /
   :func:`repro.core.sharded.sharded_two_prong_batch`) — same plans, computed
   SPMD instead of on host mirrors.
3. **Shared fetch** — the union of all planned blocks is deduplicated and each
   block is fetched exactly once per batch (including across refill rounds:
   a block fetched in round 0 for query A is served from the cache when query
   B plans it in round 2).  Physical I/O goes through the **engine-lifetime**
   LRU (:mod:`repro.core.block_cache`), so blocks warmed by earlier batches
   or ``any_k`` calls are not read from the store at all, and repeated
   (template, exclusion) plan orders are memoized across batches — a repeat
   wave skips both the THRESHOLD sort and the store reads entirely.

4. **Device-resident planning** (``plan_on_host=False``) — the default loop
   above still consults host mirrors every round (``np.asarray`` of the
   sorted orders, host prefix cuts, host window diffs).  The device pipeline
   instead carries a :class:`DevicePlanState` across refill rounds as jax
   Arrays (base combined matrix, exclusion masks, planned-prefix cursors) and
   runs combine → θ-stats → plan → block-cut entirely on device
   (:mod:`repro.kernels.plan_wave`; one ``shard_map`` collective per round
   when a sharded ``planner`` is attached).  Exactly ONE device→host transfer
   per round ships the packed ``[Q, λ]`` plan (plus per-query cut offsets)
   back for fetching — counted in ``BatchQueryResult.device_transfers`` and
   wrapped in ``jax.transfer_guard_device_to_host("allow")`` so callers can
   run the whole loop under a ``"disallow"`` guard to catch stray transfers.
   The host stays an I/O peripheral: it decodes the packed plans, applies the
   §7.2 ``auto`` cost comparison (the cost model is host-side float64), and
   uploads only the per-query choice codes + needs for the next round.

Per-query refill semantics are preserved exactly: each query's plan trajectory
(combined densities, exclusions, needs, refill rounds) is bit-identical to what
:meth:`NeedleTailEngine.any_k` would compute for it alone, so per-query results
are byte-identical to the sequential engine — only the physical I/O schedule
changes.  The host-mirror path (``plan_on_host=True``, the default) is the
byte-identity oracle for the device pipeline; it alone feeds the
:class:`~repro.core.block_cache.PlanOrderCache` memo (device rounds never
read or write it — their plans live on device, so there are no row bytes to
key on — and therefore cannot poison it).  This admission → batch plan →
shared fetch seam is what the sharding and async-serving follow-ons build on.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import OrderedDict
from typing import TYPE_CHECKING, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.density_map import AND, combine_densities_batch_np, pack_row_matrix
from repro.core.forward_optimal import forward_optimal_faithful
from repro.core.predicates import Predicate
from repro.core.threshold import threshold_cut, threshold_sort_batch
from repro.core.two_prong import two_prong_select_batch

# repro.kernels.plan_wave is imported lazily inside the device-pipeline
# functions: pulling it here would make every host-only any_k_batch call pay
# the Pallas import.

if TYPE_CHECKING:  # pragma: no cover - typing only
    from repro.core.engine import NeedleTailEngine, QueryResult


@dataclasses.dataclass(frozen=True)
class BatchQuery:
    """One admission-queue entry: a LIMIT-k query over ⊕-combined predicates.

    ``algo`` overrides the batch-level algorithm for this query; ``None``
    (default) inherits the ``algo`` argument of the ``any_k_batch`` call.
    """

    predicates: Sequence[tuple[int, int]] | Predicate
    k: int
    op: str = AND
    algo: str | None = None


@dataclasses.dataclass
class BatchQueryResult:
    """Per-query results plus the batch-level shared-fetch accounting.

    ``unique_blocks_fetched`` is the deduplicated set of blocks the batch
    *touched* (logical I/O).  With the engine-lifetime LRU
    (:mod:`repro.core.block_cache`) the *physical* story can be smaller:
    ``store_blocks_fetched`` counts blocks actually read from the store this
    batch (0 on a fully warm cache) and ``cache_hits`` counts gathers served
    from cache.
    """

    results: list["QueryResult"]
    unique_blocks_fetched: np.ndarray  # every block touched, exactly once
    blocks_requested_total: int  # Σ over queries/rounds of planned fetches
    rounds: int  # waves executed
    cpu_time_s: float
    modeled_io_s: float  # one shared pass over unique touched blocks
    store_blocks_fetched: int = 0  # physical store reads (cache misses)
    modeled_store_io_s: float = 0.0  # one pass over only the missed blocks
    cache_hits: int = 0  # block gathers served from the engine LRU
    # device pipeline only (plan_on_host=False): device→host transfers shipped
    # by the plan loop — exactly one packed plan per planning round when
    # healthy (``rounds`` executed waves plus at most one final round whose
    # plans come up empty and terminate the loop), 0 on the host-mirror path.
    # The CI guard asserts transfers <= rounds + 1.
    device_transfers: int = 0
    # tiered storage only (engine.block_cache is a repro.storage.TierStack):
    # this batch's per-tier placement deltas, keyed "<tier>.<counter>" (e.g.
    # "hbm.hits", "dram.demotions_in") — the ledger benchmarks and tests
    # assert placement behavior with.  None on a flat-LRU engine.
    tier_stats: dict | None = None
    # number of still-active queries at each executed refill round — the
    # serving layer derives slot occupancy (busy-slot fraction per round)
    # from it; len(active_per_round) == rounds.
    active_per_round: list = dataclasses.field(default_factory=list)

    @property
    def num_queries(self) -> int:
        return len(self.results)

    @property
    def dedup_ratio(self) -> float:
        """Planned block fetches per unique block touched (≥ 1; higher = more
        sharing).  Guarded: an empty batch (no query planned any block)
        reports 1.0 — no sharing, but no division by zero."""
        u = int(self.unique_blocks_fetched.size)
        if u == 0 or self.blocks_requested_total == 0:
            return 1.0
        return float(self.blocks_requested_total) / u

    @property
    def store_dedup_ratio(self) -> float:
        """Planned block fetches per *physical* store read.  On a fully warm
        cache the store reads 0 blocks; that is reported as ``inf`` (every
        planned fetch amortized), and an empty batch reports 1.0."""
        if self.blocks_requested_total == 0:
            return 1.0
        if self.store_blocks_fetched == 0:
            return float("inf")
        return float(self.blocks_requested_total) / self.store_blocks_fetched


@dataclasses.dataclass
class _QueryState:
    query: BatchQuery
    need: int
    got: int = 0
    rounds: int = 0
    done: bool = False
    used_algo: str = ""
    exclude: np.ndarray = dataclasses.field(
        default_factory=lambda: np.asarray([], dtype=np.int64)
    )
    planned: list[np.ndarray] = dataclasses.field(default_factory=list)
    rec_blocks: list[np.ndarray] = dataclasses.field(default_factory=list)
    rec_rows: list[np.ndarray] = dataclasses.field(default_factory=list)
    meas: list[np.ndarray] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class DevicePlanState:
    """Round-carried device residency of the wave planner.

    The device pipeline's inversion of data-flow ownership: the planning
    state lives on the device(s) as jax Arrays and the host touches it only
    through one packed transfer per refill round.  ``combined0`` is the base
    ⊕-combined wave matrix (computed once, exclusion-free); ``excl`` is the
    per-query exclusion mask the device updates itself from the host's choice
    codes (:func:`repro.kernels.plan_wave.apply_chosen`); ``th_mask`` /
    ``tp_win`` are the previous round's planned-prefix cursors (THRESHOLD
    selection mask and TWO-PRONG window) the replay reconstructs fetched
    block sets from.  ``transfers`` is the host-side ledger of device→host
    transfers the plan loop shipped — the quantity the ≤1-per-round CI guard
    enforces.
    """

    combined0: jax.Array  # [Qb, λ] f32 base combined densities (no exclusions)
    excl: jax.Array  # [Qb, λ] bool blocks already planned/fetched per query
    th_mask: jax.Array  # [Qb, λ] bool previous round's THRESHOLD prefix
    tp_win: jax.Array  # [Qb, 2] i32 previous round's TWO-PRONG window
    transfers: int = 0


def _bucket(n: int) -> int:
    """Next power of two ≥ n: bounds vmapped-planner recompilations."""
    b = 1
    while b < n:
        b *= 2
    return b


# Padded-row device-buffer cache (bugfix): _pad_rows used to re-pad and
# re-upload identical row sets every round — one fresh host copy plus one
# host→device transfer per planner call even when the wave re-planned the
# exact same (template, exclusion) rows.  Keys are a 16-byte blake2b digest
# of the row bytes (+ shape/dtype), not the bytes themselves, so a cached
# entry retains only the device buffer; eviction is LRU, bounded by both an
# entry count and a device-byte budget.
_PAD_CACHE: "OrderedDict[tuple, jax.Array]" = OrderedDict()
_PAD_CACHE_MAX = 128
_PAD_CACHE_MAX_BYTES = 256 << 20
_pad_cache_stats = {"hits": 0, "misses": 0, "nbytes": 0}


def _pad_rows_device(rows: np.ndarray) -> jax.Array:
    """Padded ``[bucket, λ]`` DEVICE buffer for a host row set, memoized on
    the row-set fingerprint.

    Padding to a power-of-two row count bounds vmapped-planner
    recompilations (one compile per bucket size); padded rows are zeros and
    their outputs are never read.  Reuse cases: the threshold and two-prong
    passes of one ``auto`` wave plan the same miss rows, and repeat waves on
    a cold plan memo re-upload identical row sets round after round.
    """
    import hashlib

    key = (
        hashlib.blake2b(rows.tobytes(), digest_size=16).digest(),
        rows.shape, str(rows.dtype),
    )
    buf = _PAD_CACHE.get(key)
    if buf is not None:
        _pad_cache_stats["hits"] += 1
        _PAD_CACHE.move_to_end(key)
        return buf
    _pad_cache_stats["misses"] += 1
    b = _bucket(rows.shape[0])
    if b != rows.shape[0]:
        padded = np.zeros((b, rows.shape[1]), dtype=rows.dtype)
        padded[: rows.shape[0]] = rows
        rows = padded
    buf = jnp.asarray(rows)
    _PAD_CACHE[key] = buf
    _pad_cache_stats["nbytes"] += int(buf.nbytes)
    while len(_PAD_CACHE) > _PAD_CACHE_MAX or (
        len(_PAD_CACHE) > 1 and _pad_cache_stats["nbytes"] > _PAD_CACHE_MAX_BYTES
    ):
        _, old = _PAD_CACHE.popitem(last=False)
        _pad_cache_stats["nbytes"] -= int(old.nbytes)
    return buf


def _combined_matrix(engine: "NeedleTailEngine", states: list[_QueryState]) -> np.ndarray:
    """[Qa, λ] combined densities, exclusions applied — one pass per ⊕ group."""
    lam = engine.store.num_blocks
    out = np.zeros((len(states), lam), dtype=np.float32)
    # group pair-predicate queries by op so each group is one batched combine
    groups: dict[str, list[int]] = {}
    for i, st in enumerate(states):
        if isinstance(st.query.predicates, Predicate):
            out[i] = np.asarray(
                st.query.predicates.density(engine.store.index), dtype=np.float32
            )
        else:
            groups.setdefault(st.query.op, []).append(i)
    vocab = engine.store.index.vocab
    for op, idxs in groups.items():
        rm = pack_row_matrix(vocab, [states[i].query.predicates for i in idxs])
        out[idxs] = combine_densities_batch_np(engine._dens_np, rm, op)
    for i, st in enumerate(states):
        if st.exclude.size:
            out[i, st.exclude] = 0.0
    return out


def _plan_wave(
    engine: "NeedleTailEngine", states: list[_QueryState], algo: str,
    planner=None,
) -> list[np.ndarray]:
    """Vectorized plan for one wave of active queries.

    Returns each query's planned block ids (pre-exclusion-diff), bit-identical
    to ``engine.plan`` run per query.  Cross-query plan sharing: THRESHOLD
    plans for any k over one combined row are prefixes of one density-sorted
    order, so the device work is one vmapped sort over the *unique* rows of
    the wave (hot workloads repeat a few predicate templates) and each query
    cuts its own prefix; TWO-PRONG dedups on (row, need) pairs.

    With a ``planner`` (:class:`repro.core.sharded.DistributedAnyK`), the
    THRESHOLD and TWO-PRONG selections run as one ``shard_map`` collective
    for the whole wave instead of host-mirror sorts; plans are identical as
    block-id sets (the engine's ascending §4.1 fetch sort erases the order
    difference), TWO-PRONG windows are bit-identical (group=1), and the
    ``auto`` cost comparison is order-insensitive — so downstream results
    stay byte-identical.  ``forward_optimal`` is inherently sequential
    (greedy over the cost DP) and always plans on the host.
    """
    combined = _combined_matrix(engine, states)
    rpb = engine.store.records_per_block
    needs = np.asarray([float(st.need) for st in states], dtype=np.float32)

    if algo == "forward_optimal":
        plans = []
        for st, comb in zip(states, combined):
            sel, _ = forward_optimal_faithful(comb, st.need, rpb, engine.cost)
            plans.append(np.asarray(sel, dtype=np.int64))
            st.used_algo = algo
        return plans

    qa = len(states)
    # unique combined rows of the wave (byte-keyed: exclusions already applied)
    row_key = [c.tobytes() for c in combined]
    row_of: dict[bytes, int] = {}
    uniq_rows: list[int] = []
    for i, key in enumerate(row_key):
        if key not in row_of:
            row_of[key] = len(uniq_rows)
            uniq_rows.append(i)
    u_idx = np.asarray([row_of[key] for key in row_key])

    plan_cache = engine.plan_cache

    def threshold_plans() -> list[np.ndarray]:
        # cross-batch memo: a (template, exclusion) pair is one combined-row
        # byte string; repeats across waves/batches skip the device sort
        entries: list = [None] * len(uniq_rows)
        miss: list[int] = []  # positions in uniq_rows needing a fresh sort
        for j, i in enumerate(uniq_rows):
            hit = plan_cache.get_threshold(row_key[i])
            if hit is not None:
                entries[j] = hit
            else:
                miss.append(j)
        if miss:
            rows = combined[[uniq_rows[j] for j in miss]]
            si, sd, cum = threshold_sort_batch(_pad_rows_device(rows))
            si, sd, cum = np.asarray(si), np.asarray(sd), np.asarray(cum)
            for off, j in enumerate(miss):
                entries[j] = (si[off], sd[off], cum[off])
                plan_cache.put_threshold(row_key[uniq_rows[j]], *entries[j])
        plans = []
        for i in range(qa):
            si_u, sd_u, cum_u = entries[u_idx[i]]
            n = threshold_cut(sd_u, cum_u, needs[i], rpb)
            plans.append(si_u[:n].astype(np.int64))
        return plans

    def _plan_unique_pairs(get, plan_misses, put) -> list:
        """Shared (unique-row, need) dedup for the per-pair planners: serve
        memo hits via ``get(i)``, batch-plan every missed pair ONCE via
        ``plan_misses(miss_indices)`` (one representative query index per
        pair), memoize via ``put(i, value)``; returns per-query values."""
        val: dict[tuple[int, float], object] = {}
        miss: list[int] = []
        pending: set[tuple[int, float]] = set()
        for i in range(qa):
            key = (int(u_idx[i]), float(needs[i]))
            if key in val or key in pending:
                continue
            hit = get(i)
            if hit is not None:
                val[key] = hit
            else:
                miss.append(i)
                pending.add(key)
        if miss:
            for i, v in zip(miss, plan_misses(miss)):
                val[(int(u_idx[i]), float(needs[i]))] = v
                put(i, v)
        return [val[(int(u_idx[i]), float(needs[i]))] for i in range(qa)]

    def two_prong_plans() -> list[np.ndarray]:
        def plan_misses(miss: list[int]) -> list[tuple[int, int]]:
            k_u = np.ones((_bucket(len(miss)),), dtype=np.float32)
            k_u[: len(miss)] = needs[miss]
            r = two_prong_select_batch(
                _pad_rows_device(combined[miss]), jnp.asarray(k_u), rpb
            )
            starts, ends = np.asarray(r.start), np.asarray(r.end)
            return [(int(starts[o]), int(ends[o])) for o in range(len(miss))]

        wins = _plan_unique_pairs(
            lambda i: plan_cache.get_two_prong(row_key[i], float(needs[i])),
            plan_misses,
            lambda i, w: plan_cache.put_two_prong(row_key[i], float(needs[i]), *w),
        )
        return [np.arange(*w, dtype=np.int64) for w in wins]

    def threshold_plans_sharded() -> list[np.ndarray]:
        # one shard_map collective plans every missed (row, need) pair; the
        # memo stores materialized id sets (the sharded planner returns the
        # selected prefix, not the full sorted order the host memo keeps)
        return _plan_unique_pairs(
            lambda i: plan_cache.get_sharded_threshold(row_key[i], float(needs[i])),
            lambda miss: planner.threshold_plan_wave(combined[miss], needs[miss]),
            lambda i, ids: plan_cache.put_sharded_threshold(
                row_key[i], float(needs[i]), ids
            ),
        )

    def two_prong_plans_sharded() -> list[np.ndarray]:
        # group=1 windows are bit-identical to the host planner's, so the
        # (row, need) -> (start, end) memo is SHARED with the host path: a
        # wave planned on host warms the sharded replan and vice versa.
        # group>1 windows are group-aligned (up to G wider per side) —
        # memoizing them would poison the exact host memo, so they bypass it.
        exact = getattr(planner, "two_prong_group", 1) == 1
        wins = _plan_unique_pairs(
            (lambda i: plan_cache.get_two_prong(row_key[i], float(needs[i])))
            if exact else (lambda i: None),
            lambda miss: planner.two_prong_plan_wave(combined[miss], needs[miss]),
            (lambda i, w: plan_cache.put_two_prong(row_key[i], float(needs[i]), *w))
            if exact else (lambda i, w: None),
        )
        return [np.arange(int(s), int(e), dtype=np.int64) for s, e in wins]

    if planner is not None:
        threshold_plans = threshold_plans_sharded
        two_prong_plans = two_prong_plans_sharded

    if algo == "threshold":
        plans = threshold_plans()
        for st in states:
            st.used_algo = algo
        return plans
    if algo == "two_prong":
        plans = two_prong_plans()
        for st in states:
            st.used_algo = algo
        return plans
    if algo == "auto":
        # §7.2: plan with both, cost both, take the cheaper — per query.
        # plan_cost prices by effective tier cost on a residency-aware tiered
        # engine (getattr: tolerate engine shims built without __init__).
        cost_fn = getattr(engine, "plan_cost", None) or engine.cost.io_time
        pt, p2 = threshold_plans(), two_prong_plans()
        plans = []
        for st, bt, b2 in zip(states, pt, p2):
            ct, c2 = cost_fn(bt), cost_fn(b2)
            if ct <= c2:
                plans.append(bt)
                st.used_algo = "threshold"
            else:
                plans.append(b2)
                st.used_algo = "two_prong"
        return plans
    raise ValueError(f"unknown algo {algo!r}")


def _execute_wave(
    engine: "NeedleTailEngine",
    cache,
    active: list[_QueryState],
    wave_blocks: list[np.ndarray],
    touched: list[int],
    touched_set: set[int],
) -> tuple[bool, int]:
    """Fetch one wave's deduplicated union and apply each query's §4.1
    post-fetch bookkeeping (mask, record append, exclusion growth, refill
    accounting).  Shared verbatim by the host-mirror and device plan loops so
    the only thing that differs between them is where plans are computed.
    Returns ``(progressed, blocks_requested_delta)``."""
    obs = getattr(engine, "obs", None)
    if obs is not None:
        with obs.span("wave.execute", n_active=len(active)) as sp:
            progressed, requested = _execute_wave_body(
                engine, cache, active, wave_blocks, touched, touched_set
            )
            sp.set(requested=requested, progressed=progressed,
                   satisfied=sum(1 for st in active if st.done))
            return progressed, requested
    return _execute_wave_body(
        engine, cache, active, wave_blocks, touched, touched_set
    )


def _execute_wave_body(
    engine: "NeedleTailEngine",
    cache,
    active: list[_QueryState],
    wave_blocks: list[np.ndarray],
    touched: list[int],
    touched_set: set[int],
) -> tuple[bool, int]:
    union = np.unique(np.concatenate(wave_blocks)) if wave_blocks else np.asarray([])
    if union.size:
        for b in union:
            if int(b) not in touched_set:
                touched_set.add(int(b))
                touched.append(int(b))
        cache.ensure(engine.store, union)
    progressed = False
    requested = 0
    for st, blocks in zip(active, wave_blocks):
        if blocks.size == 0:
            continue
        progressed = True
        bd, bm, bv = cache.get_many(engine.store, blocks)
        mask = np.asarray(engine._mask(bd, st.query.predicates, st.query.op) & bv)
        bi, ri = np.nonzero(mask)
        st.rec_blocks.append(blocks[bi])
        st.rec_rows.append(ri)
        st.meas.append(np.asarray(bm)[bi, ri])
        st.planned.append(blocks)
        requested += int(blocks.size)
        st.got += int(bi.size)
        st.exclude = np.concatenate([st.exclude, blocks])
        st.need = st.query.k - st.got
        st.rounds += 1
        if st.got >= st.query.k:
            st.done = True
    return progressed, requested


def new_query_state(query: "BatchQuery | tuple") -> _QueryState:
    """Fresh per-query refill state for `query` (satisfied immediately when
    ``k <= 0``).  The continuous serving loop creates states one at a time as
    requests join slots; ``run_batch`` creates a whole wave's worth."""
    q = query if isinstance(query, BatchQuery) else BatchQuery(*query)
    return _QueryState(query=q, need=q.k, done=(q.k <= 0))


def plan_round_host(
    engine: "NeedleTailEngine",
    active: list[_QueryState],
    algo: str,
    planner=None,
) -> list[np.ndarray]:
    """Plan ONE refill round for `active` (not-done) states on host mirrors.

    The single-round body of :func:`_host_plan_loop`, reusable by the
    continuous serving loop (which re-plans a slot pool whose membership
    changes between rounds): per-query algo groups each plan in one
    :func:`_plan_wave` call, then each state's plan is diffed against its
    exclusions (§4.1: ``setdiff1d`` returns ascending fetch order).  A state
    whose diff comes up empty is marked done (plan exhausted).  Returns the
    per-state block sets, aligned with `active`, ready for
    :func:`_execute_wave`.
    """
    obs = getattr(engine, "obs", None)
    if obs is not None:
        site = "sharded" if planner is not None else "host"
        with obs.span("plan.round", site=site, n_active=len(active)) as sp:
            wave_blocks = _plan_round_host_body(engine, active, algo, planner)
            union = (np.unique(np.concatenate(wave_blocks))
                     if wave_blocks else np.asarray([], dtype=np.int64))
            choices: dict[str, int] = {}
            for st in active:
                choices[st.used_algo] = choices.get(st.used_algo, 0) + 1
            sp.set(n_blocks=int(union.size), choices=choices,
                   predicted_io_s=float(engine.cost.io_time(union)))
            return wave_blocks
    return _plan_round_host_body(engine, active, algo, planner)


def _plan_round_host_body(
    engine: "NeedleTailEngine",
    active: list[_QueryState],
    algo: str,
    planner=None,
) -> list[np.ndarray]:
    by_algo: dict[str, list[_QueryState]] = {}
    for st in active:
        by_algo.setdefault(st.query.algo or algo, []).append(st)
    plan_of: dict[int, np.ndarray] = {}
    for a, group in by_algo.items():
        for st, plan in zip(group, _plan_wave(engine, group, a, planner)):
            plan_of[id(st)] = plan
    wave_blocks: list[np.ndarray] = []
    for st in active:
        blocks = np.setdiff1d(plan_of[id(st)], st.exclude)
        if blocks.size == 0:
            st.done = True  # plan exhausted: nothing new to read
        wave_blocks.append(blocks)
    return wave_blocks


def _host_plan_loop(
    engine: "NeedleTailEngine",
    states: list[_QueryState],
    algo: str,
    planner,
    cache,
    touched: list[int],
    touched_set: set[int],
    active_counts: list[int] | None = None,
) -> tuple[int, int]:
    """The host-mirror refill loop (the byte-identity oracle): plans on host
    mirrors via :func:`plan_round_host`, one shared union fetch per wave.
    Returns ``(waves, blocks_requested_total)``."""
    requested_total = 0
    waves = 0
    while waves < engine.max_refills:
        active = [st for st in states if not st.done]
        if not active:
            break
        wave_blocks = plan_round_host(engine, active, algo, planner)
        progressed, req = _execute_wave(
            engine, cache, active, wave_blocks, touched, touched_set
        )
        requested_total += req
        if not progressed:
            break
        waves += 1
        if active_counts is not None:
            active_counts.append(len(active))
    return waves, requested_total


def finalize_query_result(
    engine: "NeedleTailEngine",
    st: _QueryState,
    default_algo: str = "auto",
    cpu_time_s: float = 0.0,
):
    """Assemble the public :class:`~repro.core.engine.QueryResult` from a
    finished (or retired) refill state.  Shared by ``run_batch`` (per wave
    member at batch end) and the continuous serving loop (per slot the
    instant it leaves)."""
    from repro.core.engine import QueryResult

    all_blocks = (
        np.concatenate(st.planned) if st.planned else np.asarray([], dtype=np.int64)
    )
    return QueryResult(
        record_block=np.concatenate(st.rec_blocks)
        if st.rec_blocks
        else np.asarray([], np.int64),
        record_row=np.concatenate(st.rec_rows)
        if st.rec_rows
        else np.asarray([], np.int64),
        measures=np.concatenate(st.meas)
        if st.meas
        else np.zeros((0, 0), np.float32),
        blocks_fetched=all_blocks,
        algo=st.used_algo or (st.query.algo or default_algo),
        cpu_time_s=cpu_time_s,  # wave time is shared; a per-query share is not meaningful
        modeled_io_s=engine.cost.io_time(all_blocks),
        plan_rounds=st.rounds,
    )


@functools.lru_cache(maxsize=None)
def _local_round_fn(records_per_block: int):
    """Jitted single-device round body of the device pipeline (memoized per
    block capacity; jax caches per wave shape).  One call = replay last
    round's choices onto the exclusion mask, re-plan every query on device,
    and pack the round's plans into the single-transfer matrix."""
    from repro.kernels.plan_wave import (
        apply_chosen, pack_plan, plan_wave_from_combined,
    )

    def round_fn(combined0, excl, th_prev, tp_prev, chosen_prev, needs):
        excl = apply_chosen(excl, th_prev, tp_prev, chosen_prev)
        res = plan_wave_from_combined(combined0, excl, needs, records_per_block)
        packed = pack_plan(res.th_mask, res.n_sel, res.tp_start, res.tp_end)
        tp_win = jnp.stack([res.tp_start, res.tp_end], axis=1)
        return packed, excl, res.th_mask, tp_win

    return jax.jit(round_fn)


_DEVICE_ALGOS = ("threshold", "two_prong", "auto", "forward_optimal")


class DeviceWave:
    """A slot-pooled device-resident wave planner.

    Owns a fixed ``[Qb, λ]`` :class:`DevicePlanState` whose rows are serving
    *slots*: queries :meth:`join` a slot between refill rounds and
    :meth:`leave` the instant they are satisfied, so the wave's effective Q
    axis shrinks and grows without reallocating device state or recompiling
    the round body.  Departures are host-side only (active mask + choice
    code cleared — a stale row is never replayed and its plan outputs are
    not decoded); joins batch into ONE device scatter per round
    (:func:`repro.kernels.plan_wave.join_wave_slots`), flushed lazily at the
    top of :meth:`plan_round`.  Rows are planned independently, so each
    occupant's plan trajectory is bit-identical to a solo run whatever the
    other slots hold, and each round still ships exactly one packed
    device→host transfer (``state.transfers`` is the ledger the CI guard
    audits).

    ``run_batch(plan_on_host=False)`` drives a throwaway DeviceWave with one
    slot per query; the continuous serving loop keeps one alive across
    requests (``repro.serving.engine.ServeEngine``).
    """

    def __init__(
        self,
        engine: "NeedleTailEngine",
        n_slots: int,
        default_algo: str = "auto",
        planner=None,
    ):
        if default_algo not in _DEVICE_ALGOS:
            raise ValueError(f"unknown algo {default_algo!r}")
        self.engine = engine
        self.planner = planner
        self.default_algo = default_algo
        self.n_slots = n_slots
        self.lam = engine.store.num_blocks
        self.rpb = engine.store.records_per_block
        self.qb = _bucket(max(n_slots, 1))
        if planner is not None:
            self.round_fn = planner.device_round_fn(self.lam, self.rpb)
        else:
            self.round_fn = _local_round_fn(self.rpb)
        self.state = DevicePlanState(
            combined0=jnp.zeros((self.qb, self.lam), jnp.float32),
            excl=jnp.zeros((self.qb, self.lam), bool),
            th_mask=jnp.zeros((self.qb, self.lam), bool),
            tp_win=jnp.zeros((self.qb, 2), jnp.int32),
        )
        self.chosen = np.full((self.qb,), -1, np.int8)
        self.slots: list[_QueryState | None] = [None] * n_slots
        self._joining: list[int] = []

    @property
    def transfers(self) -> int:
        return self.state.transfers

    def busy_slots(self) -> list[int]:
        return [s for s in range(self.n_slots) if self.slots[s] is not None]

    def join(self, slot: int, st: _QueryState) -> None:
        """Seat `st` at `slot` (must be free); its base combined row and any
        prior exclusions are scattered into the device state on the next
        :meth:`plan_round` (one batched scatter for all joiners)."""
        if self.slots[slot] is not None:
            raise ValueError(f"slot {slot} is occupied")
        if (st.query.algo or self.default_algo) not in _DEVICE_ALGOS:
            raise ValueError(f"unknown algo {st.query.algo!r}")
        self.slots[slot] = st
        self.chosen[slot] = -1
        self._joining.append(slot)

    def leave(self, slot: int) -> _QueryState | None:
        """Vacate `slot`.  Host-side only: the stale device row is inert
        (choice code -1 is never replayed; outputs of inactive rows are not
        decoded) and will be overwritten by the next joiner's scatter."""
        st = self.slots[slot]
        self.slots[slot] = None
        self.chosen[slot] = -1
        if slot in self._joining:  # joined and left without ever planning
            self._joining.remove(slot)
        return st

    def _flush_joins(self) -> None:
        """One ⊕-combine per op group for the queued joiners (the
        :func:`repro.kernels.plan_wave.combine_wave` fold — bit-identical to
        the host combine; Predicate trees compile host-side and upload), then
        one scatter seats them all."""
        if not self._joining:
            return
        from repro.kernels.plan_wave import combine_wave, join_wave_slots

        joining, self._joining = self._joining, []
        engine = self.engine
        dens_dev = engine.store.index.densities  # [rows, λ] jax Array, resident
        vocab = engine.store.index.vocab
        rows: list = [None] * len(joining)
        groups: dict[str, list[int]] = {}
        for j, slot in enumerate(joining):
            st = self.slots[slot]
            if isinstance(st.query.predicates, Predicate):
                rows[j] = jnp.asarray(
                    np.asarray(
                        st.query.predicates.density(engine.store.index),
                        dtype=np.float32,
                    )
                )
            else:
                groups.setdefault(st.query.op, []).append(j)
        for op, js in groups.items():
            rm = pack_row_matrix(
                vocab, [self.slots[joining[j]].query.predicates for j in js]
            )
            rows_dev = combine_wave(dens_dev, jnp.asarray(rm), op)
            for off, j in enumerate(js):
                rows[j] = rows_dev[off]
        excl_rows = np.zeros((len(joining), self.lam), dtype=bool)
        for j, slot in enumerate(joining):
            ex = self.slots[slot].exclude
            if ex.size:
                excl_rows[j, ex] = True
        c0, ex, th, tp = join_wave_slots(
            self.state.combined0, self.state.excl, self.state.th_mask,
            self.state.tp_win, jnp.asarray(np.asarray(joining, np.int32)),
            jnp.stack(rows), jnp.asarray(excl_rows),
        )
        self.state.combined0, self.state.excl = c0, ex
        self.state.th_mask, self.state.tp_win = th, tp

    def plan_round(self) -> tuple[list[_QueryState], list[np.ndarray]]:
        """One device planning round over the current occupants.

        Flush queued joins, replay last round's choice codes onto the
        exclusion masks, re-plan every slot on device, and ship the round's
        single packed transfer; the host decodes only the occupied rows
        (forward_optimal occupants plan on the host DP as ever).  Returns
        ``(active_states, wave_blocks)`` in slot order, ready for
        :func:`_execute_wave` — both empty when no slot is occupied (in
        which case no transfer is shipped).
        """
        self._flush_joins()
        active_slots = self.busy_slots()
        active = [self.slots[s] for s in active_slots]
        if not active:
            return [], []
        from repro.kernels.plan_wave import unpack_plan

        engine = self.engine
        dstate = self.state
        needs_np = np.ones((self.qb,), np.float32)
        for s, st in zip(active_slots, active):
            needs_np[s] = float(st.need)
        packed, excl, th_prev, tp_prev = self.round_fn(
            dstate.combined0, dstate.excl, dstate.th_mask, dstate.tp_win,
            jnp.asarray(self.chosen), jnp.asarray(needs_np),
        )
        dstate.excl, dstate.th_mask, dstate.tp_win = excl, th_prev, tp_prev
        # the round's single device→host transfer: the packed [Q, λ+3] plan.
        # Explicitly allowed so callers can run the whole loop under
        # jax.transfer_guard_device_to_host("disallow") as a stray-transfer
        # probe (benchmarks/common.py).
        with jax.transfer_guard_device_to_host("allow"):
            packed_np = np.asarray(packed)
        dstate.transfers += 1
        obs = getattr(engine, "obs", None)
        if obs is not None:
            obs.event("device.transfer", n=dstate.transfers,
                      nbytes=int(packed_np.nbytes), n_active=len(active))
        th_mask, _, tps, tpe = unpack_plan(packed_np, self.lam)
        # forward_optimal falls back to the host DP (sequential by nature);
        # its combined rows come from the host mirror, not the device
        fo_active = [
            st for st in active
            if (st.query.algo or self.default_algo) == "forward_optimal"
        ]
        fo_plans: dict[int, np.ndarray] = {}
        if fo_active:
            fo_combined = _combined_matrix(engine, fo_active)
            for st, comb in zip(fo_active, fo_combined):
                sel, _ = forward_optimal_faithful(comb, st.need, self.rpb, engine.cost)
                fo_plans[id(st)] = np.asarray(sel, dtype=np.int64)
        self.chosen = np.full((self.qb,), -1, np.int8)
        wave_blocks: list[np.ndarray] = []
        for s, st in zip(active_slots, active):
            a = st.query.algo or self.default_algo
            if a == "forward_optimal":
                plan = fo_plans[id(st)]
                st.used_algo = a
            elif a == "threshold":
                plan = np.flatnonzero(th_mask[s]).astype(np.int64)
                self.chosen[s] = 0
                st.used_algo = a
            elif a == "two_prong":
                plan = np.arange(int(tps[s]), int(tpe[s]), dtype=np.int64)
                self.chosen[s] = 1
                st.used_algo = a
            else:  # auto — §7.2: cost both on host (the cost model is f64 host code)
                bt = np.flatnonzero(th_mask[s]).astype(np.int64)
                b2 = np.arange(int(tps[s]), int(tpe[s]), dtype=np.int64)
                cost_fn = getattr(engine, "plan_cost", None) or engine.cost.io_time
                ct, c2 = cost_fn(bt), cost_fn(b2)
                if ct <= c2:
                    plan, self.chosen[s], st.used_algo = bt, 0, "threshold"
                else:
                    plan, self.chosen[s], st.used_algo = b2, 1, "two_prong"
            blocks = np.setdiff1d(plan, st.exclude)
            if blocks.size == 0:
                st.done = True  # plan exhausted: nothing new to read
            wave_blocks.append(blocks)
        if obs is not None:
            choices: dict[str, int] = {}
            for st in active:
                choices[st.used_algo] = choices.get(st.used_algo, 0) + 1
            union = (np.unique(np.concatenate(wave_blocks))
                     if wave_blocks else np.asarray([], dtype=np.int64))
            obs.event("plan.round", site="device", n_active=len(active),
                      n_blocks=int(union.size), choices=choices,
                      predicted_io_s=float(engine.cost.io_time(union)))
        return active, wave_blocks


def _device_plan_loop(
    engine: "NeedleTailEngine",
    states: list[_QueryState],
    algo: str,
    planner,
    cache,
    touched: list[int],
    touched_set: set[int],
    active_counts: list[int] | None = None,
) -> tuple[int, int, int]:
    """The device-resident refill loop: combine → θ-stats → plan → block-cut
    on device, ONE device→host transfer per round.

    One :class:`DeviceWave` slot per query: all states join up front and each
    leaves the round it is satisfied; with a sharded ``planner`` each round's
    plan step is one ``shard_map`` collective whose outputs feed the device
    cut directly (:meth:`repro.core.sharded.DistributedAnyK.device_round_fn`
    — no host mirrors between plan and cut).  Per-query results are
    byte-identical to the ``plan_on_host=True`` oracle; ``forward_optimal``
    queries (inherently sequential, host cost DP) ride the wave but plan on
    host.  Returns ``(waves, blocks_requested_total, device_transfers)``.
    """
    for a in set(st.query.algo or algo for st in states):
        if a not in _DEVICE_ALGOS:
            raise ValueError(f"unknown algo {a!r}")
    wave = DeviceWave(engine, len(states), default_algo=algo, planner=planner)
    for i, st in enumerate(states):
        if not st.done:
            wave.join(i, st)
    requested_total = 0
    waves = 0
    while waves < engine.max_refills:
        active, wave_blocks = wave.plan_round()
        if not active:
            break
        progressed, req = _execute_wave(
            engine, cache, active, wave_blocks, touched, touched_set
        )
        requested_total += req
        for s in wave.busy_slots():
            if wave.slots[s].done:
                wave.leave(s)
        if not progressed:
            break
        waves += 1
        if active_counts is not None:
            active_counts.append(len(active))
    return waves, requested_total, wave.transfers


def run_batch(
    engine: "NeedleTailEngine",
    queries: Sequence[BatchQuery | tuple],
    algo: str = "auto",
    planner=None,
    plan_on_host: bool = True,
) -> BatchQueryResult:
    """Evaluate Q any-k queries with shared-fetch scheduling.

    Each query's returned records are byte-identical to
    ``engine.any_k(q.predicates, q.k, q.op, q.algo or algo)`` — same blocks
    planned, same refill rounds, same record order.  Physical I/O goes
    through the engine-lifetime LRU (:attr:`NeedleTailEngine.block_cache`):
    within the batch every block is read from the store at most once
    (provided the byte budget covers the working set), and blocks cached by
    earlier batches or ``any_k`` calls are not read at all.

    ``planner`` (a :class:`repro.core.sharded.DistributedAnyK`) swaps the
    host-mirror plan step for sharded batched planning: each refill round's
    plan wave is ONE ``shard_map`` collective over the mesh, and the
    byte-identity guarantee above is preserved (the sharded planners are
    exact).  Most callers go through
    :meth:`NeedleTailEngine.any_k_batch` / :meth:`DistributedAnyK.any_k_batch`
    rather than passing ``planner`` directly.

    ``plan_on_host=False`` selects the device-resident pipeline
    (:func:`_device_plan_loop`): the plan state stays on device across refill
    rounds and exactly one device→host transfer per round ships the packed
    plans (``BatchQueryResult.device_transfers`` counts them).  The default
    ``True`` keeps the host-mirror loop — the byte-identity oracle, and the
    only path that feeds the :class:`~repro.core.block_cache.PlanOrderCache`
    memo.
    """
    obs = getattr(engine, "obs", None)
    sp = obs.span("batch.run", n_queries=len(queries),
                  site="host" if plan_on_host else "device") if obs is not None \
        else None
    if sp is not None:
        sp.__enter__()
    t0 = time.perf_counter()
    states = [new_query_state(q) for q in queries]
    cache = engine.block_cache
    hits0 = cache.stats.hits
    store0 = cache.stats.store_blocks_fetched
    # tiered storage (repro.storage.TierStack): snapshot the per-tier
    # placement counters so this batch's deltas ride out on the result
    tier_fn = getattr(cache, "tier_counters", None)
    tier0 = tier_fn() if tier_fn is not None else None
    touched: list[int] = []  # batch-touched unique block ids, first-touch order
    touched_set: set[int] = set()
    missed: list[np.ndarray] = []  # ids physically read from the store
    prev_log, cache.fetch_log = cache.fetch_log, missed
    requested_total = 0
    waves = 0
    device_transfers = 0
    active_counts: list[int] = []

    try:
        if engine.store.num_blocks == 0 or not any(not st.done for st in states):
            pass  # λ=0 store or an all-satisfied wave: nothing to plan or fetch
        elif plan_on_host:
            waves, requested_total = _host_plan_loop(
                engine, states, algo, planner, cache, touched, touched_set,
                active_counts=active_counts,
            )
        else:
            waves, requested_total, device_transfers = _device_plan_loop(
                engine, states, algo, planner, cache, touched, touched_set,
                active_counts=active_counts,
            )
    finally:
        cache.fetch_log = prev_log

    cpu = time.perf_counter() - t0
    results = [
        finalize_query_result(engine, st, default_algo=algo, cpu_time_s=cpu)
        for st in states
    ]
    touched_ids = np.asarray(touched, dtype=np.int64)
    if sp is not None:
        sp.set(waves=waves, requested=requested_total,
               unique_blocks=int(touched_ids.size),
               device_transfers=device_transfers,
               store_blocks_fetched=int(cache.stats.store_blocks_fetched - store0),
               cache_hits=int(cache.stats.hits - hits0))
        sp.__exit__(None, None, None)
    return BatchQueryResult(
        results=results,
        unique_blocks_fetched=touched_ids,
        blocks_requested_total=requested_total,
        rounds=waves,
        cpu_time_s=cpu,
        modeled_io_s=engine.cost.io_time(touched_ids),
        store_blocks_fetched=int(cache.stats.store_blocks_fetched - store0),
        modeled_store_io_s=sum(engine.cost.io_time(m) for m in missed),
        cache_hits=int(cache.stats.hits - hits0),
        device_transfers=device_transfers,
        tier_stats=(
            {k: v - tier0[k] for k, v in tier_fn().items()}
            if tier0 is not None
            else None
        ),
        active_per_round=active_counts,
    )
