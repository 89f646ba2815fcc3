"""Distributed any-k (the paper's "future work: distributed NeedleTail", §6).

The density-map index and block store are sharded over the mesh `data` axis
(each shard owns a contiguous range of λ/P blocks — locality-preserving).  Plans
are computed SPMD with `shard_map`:

* :func:`sharded_threshold` — exact distributed THRESHOLD: each shard selects its
  local top-C candidate blocks (sort + slice), candidates are all-gathered
  (C·P ≪ λ bytes on the wire), and every shard computes the identical global
  density-sorted prefix cutoff.  A `sufficient` flag reports whether C was large
  enough for exactness (driver refills with 2C otherwise — geometric backoff).
* :func:`sharded_two_prong` — hierarchical distributed TWO-PRONG: per-group
  (G-block) sums are all-gathered, the global minimal *group-aligned* window is
  computed identically on every shard.  The returned window is within G blocks of
  the true optimum per side; G trades collective bytes for window slack (G=1 is
  exact and bit-identical to :func:`repro.core.two_prong.two_prong_select`).
* :func:`sharded_ht_terms` — psum-reduction of per-shard Horvitz-Thompson terms.

**Batched wave planning** (the serving path): a wave of Q concurrent queries
used to pay one collective *per query*.  The ``*_batch`` forms vmap the
per-shard bodies over the query axis, so ONE ``shard_map`` collective plans the
entire ``[Q, λ]`` wave:

* :func:`sharded_threshold_batch` — vmapped frontier gather: one all-gather of
  ``Q·C·P·8`` bytes replaces Q gathers.
* :func:`sharded_two_prong_batch` — vmapped window search (G=1 default: exact).
* :func:`sharded_threshold_bisect_batch` — batched θ-bisection: per-shard
  masked ``[Q, T]`` statistics (jnp, or the
  :func:`repro.kernels.theta_stats.theta_stats_batch` Pallas kernel) merged by
  one psum of ``Q·2·T`` floats per round.

:class:`DistributedAnyK` wraps the SPMD planners for production use: wave-level
geometric candidate refill, per-query plan extraction, fetches routed through
the engine-lifetime block LRU, and :meth:`DistributedAnyK.any_k_batch` — the
mesh-native form of :meth:`repro.core.engine.NeedleTailEngine.any_k_batch`,
byte-identical per query to the host-mirror path.

Collective footprint per *wave*: one all-gather of ``Q·C·P·(4+4)`` bytes
(THRESHOLD) or ``Q·(λ/G)·4`` bytes (TWO-PRONG) — this is the term the §Perf
hillclimb drives down.
"""
from __future__ import annotations

import functools
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from jax import shard_map


class ShardedThresholdResult(NamedTuple):
    block_ids: jax.Array  # [C*P] global ids, density-desc; -1 past num_selected
    num_selected: jax.Array  # [] int32
    expected_records: jax.Array  # [] f32
    sufficient: jax.Array  # [] bool — True iff the cutoff is provably exact


def _local_threshold_body(
    combined: jax.Array,  # [lam_local] this shard's combined densities
    k: jax.Array,
    records_per_block: int,
    candidates: int,
    axis: str | tuple[str, ...],
):
    lam_local = combined.shape[0]
    axis_index = jax.lax.axis_index(axis)
    base = axis_index.astype(jnp.int32) * lam_local
    order = jnp.argsort(-combined, stable=True).astype(jnp.int32)
    top_ids = order[:candidates] + base
    top_d = combined[order[:candidates]]
    # gather candidate frontiers from all shards
    all_d = jax.lax.all_gather(top_d, axis, tiled=True)  # [C*P]
    all_ids = jax.lax.all_gather(top_ids, axis, tiled=True)
    # identical global cutoff on every shard
    g_order = jnp.argsort(-all_d, stable=True)
    g_d = all_d[g_order]
    g_ids = all_ids[g_order]
    cum = jnp.cumsum(g_d) * records_per_block
    reached = cum >= k
    any_hit = jnp.any(reached)
    first_hit = jnp.argmax(reached)
    n_sel = jnp.where(any_hit, first_hit + 1, jnp.sum(g_d > 0)).astype(jnp.int32)
    pos = jnp.arange(g_d.shape[0], dtype=jnp.int32)
    ids = jnp.where(pos < n_sel, g_ids, -1)
    exp = jnp.where(n_sel > 0, cum[jnp.maximum(n_sel - 1, 0)], 0.0)
    # exactness: no shard whose entire C-frontier was consumed could be hiding a
    # denser block than the cutoff density. If shard s contributed c_s selected
    # candidates with c_s == C, blocks beyond its frontier may exceed the cutoff.
    sel_mask = pos < n_sel
    shard_of = all_ids // lam_local
    num_shards = all_d.shape[0] // candidates  # static: gather is [C*P]
    counts = jnp.zeros((num_shards,), jnp.int32).at[
        shard_of[g_order]
    ].add(sel_mask.astype(jnp.int32))
    # NOTE: no ~any_hit escape — if the frontier can't reach k we cannot tell
    # "no more records exist" from "frontier too small"; a saturated shard
    # (counts == C) always demands a refill.
    sufficient = jnp.all(counts < candidates)
    return ids, n_sel, exp.astype(jnp.float32), sufficient


def sharded_threshold(
    combined_global: jax.Array,  # [lam] sharded over `axis`
    k: float,
    records_per_block: int,
    mesh: Mesh,
    axis: str = "data",
    candidates: int = 64,
) -> ShardedThresholdResult:
    """Exact distributed THRESHOLD for one query (one round).

    Parameters
    ----------
    combined_global : jax.Array
        ``[λ]`` ⊕-combined densities, sharded ``P(axis)`` over the mesh.
    k : float
        Requested number of valid records.
    records_per_block : int
        Block capacity R (densities are fractions of R).
    mesh : jax.sharding.Mesh
        Mesh whose ``axis`` dimension shards λ into contiguous block ranges.
    candidates : int
        Per-shard frontier size C; the wire cost is ``C·P·8`` bytes.

    Returns
    -------
    ShardedThresholdResult
        ``block_ids[:num_selected]`` is the global density-sorted prefix,
        identical to :func:`repro.core.threshold.threshold_select` whenever
        ``sufficient`` is True; otherwise re-plan with 2C (geometric backoff,
        see :meth:`DistributedAnyK.threshold_plan`).
    """
    kv = jnp.asarray(k, jnp.float32)
    body = partial(
        _local_threshold_body,
        records_per_block=records_per_block,
        candidates=candidates,
        axis=axis,
    )
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    ids, n_sel, exp, ok = fn(combined_global, kv)
    return ShardedThresholdResult(ids, n_sel, exp, ok)


class ShardedTwoProngResult(NamedTuple):
    start_block: jax.Array  # [] int32 (group-aligned)
    end_block: jax.Array  # [] int32 exclusive
    expected_records: jax.Array  # [] f32


def _local_two_prong_body(
    local: jax.Array,  # [lam_local]
    k: jax.Array,
    records_per_block: int,
    group: int,
    axis: str | tuple[str, ...],
):
    lam_local = local.shape[0]
    g = lam_local // group
    gsums = jnp.sum(local.reshape(g, group), axis=1) * records_per_block
    all_g = jax.lax.all_gather(gsums, axis, tiled=True)  # [G_total]
    c = jnp.concatenate([jnp.zeros((1,), all_g.dtype), jnp.cumsum(all_g)])
    targets = c[:-1] + k
    ends = jnp.searchsorted(c, targets, side="left").astype(jnp.int32)
    starts = jnp.arange(all_g.shape[0], dtype=jnp.int32)
    feasible = ends <= all_g.shape[0]
    lengths = jnp.where(feasible, ends - starts, jnp.iinfo(jnp.int32).max)
    best = jnp.argmin(lengths).astype(jnp.int32)
    any_f = jnp.any(feasible)
    s = jnp.where(any_f, best, 0) * group
    e = jnp.where(any_f, ends[best], all_g.shape[0]) * group
    exp = c[jnp.where(any_f, ends[best], all_g.shape[0])] - c[jnp.where(any_f, best, 0)]
    return s, e, exp.astype(jnp.float32)


def sharded_two_prong(
    combined_global: jax.Array,
    k: float,
    records_per_block: int,
    mesh: Mesh,
    axis: str = "data",
    group: int = 64,
) -> ShardedTwoProngResult:
    """Hierarchical distributed TWO-PRONG for one query.

    Parameters
    ----------
    combined_global : jax.Array
        ``[λ]`` ⊕-combined densities, sharded ``P(axis)``.
    group : int
        Aggregation granularity G: per-G-block sums are all-gathered
        (``(λ/G)·4`` bytes) and the minimal *group-aligned* window is computed.
        The window is within G blocks of the true optimum per side; ``group=1``
        is exact — bit-identical to
        :func:`repro.core.two_prong.two_prong_select`.

    Returns
    -------
    ShardedTwoProngResult
        ``[start_block, end_block)`` window and its expected record mass.
    """
    kv = jnp.asarray(k, jnp.float32)
    body = partial(
        _local_two_prong_body,
        records_per_block=records_per_block,
        group=group,
        axis=axis,
    )
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    s, e, exp = fn(combined_global, kv)
    return ShardedTwoProngResult(s, e, exp)


def sharded_ht_terms(
    tau_over_pi_local: jax.Array,  # [B_local] per-block τ_i/π_i on this shard
    n_over_pi_local: jax.Array,
    mesh: Mesh,
    axis: str = "data",
) -> tuple[jax.Array, jax.Array]:
    """Global HT numerator/denominator via psum (Eq. 1/5 across shards)."""

    def body(t, n):
        return (
            jax.lax.psum(jnp.sum(t), axis),
            jax.lax.psum(jnp.sum(n), axis),
        )

    fn = shard_map(
        body, mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=(P(), P()),
        check_vma=False,
    )
    return fn(tau_over_pi_local, n_over_pi_local)


def shard_density_maps(
    densities: jax.Array, mesh: Mesh, axis: str = "data"
) -> jax.Array:
    """Place the [rows, λ] index with λ sharded over `axis` (block ranges)."""
    return jax.device_put(densities, NamedSharding(mesh, P(None, axis)))

class ShardedBisectResult(NamedTuple):
    theta: jax.Array  # [] f32 — largest θ with ≥ k expected records above it
    num_selected: jax.Array  # [] int32 blocks with density ≥ θ
    expected_records: jax.Array  # [] f32


def sharded_threshold_bisect(
    combined_global: jax.Array,  # [lam] sharded over `axis`
    k: float,
    records_per_block: int,
    mesh: Mesh,
    axis: str | tuple[str, ...] = "data",
    rounds: int = 3,
    fanout: int = 16,
) -> ShardedBisectResult:
    """Sort-free distributed THRESHOLD via θ-bisection (kernels/theta_stats).

    Each round every shard computes masked (count, Σdensity) statistics for
    `fanout` candidate thresholds over its local blocks — a streamed reduction,
    no sort, no candidate materialization — and one psum of 2·fanout floats
    merges them fleet-wide.  This is the paper's running-threshold invariant
    evaluated directly: wire bytes per query = rounds · 2 · fanout · 4 B
    (vs. candidates·P·8 B for the gather-based planner)."""
    kv = jnp.asarray(k, jnp.float32)

    def body(local: jax.Array, kk: jax.Array):
        lo = jnp.float32(0.0)
        hi = jnp.float32(1.0 + 1e-6)
        n_sel = jnp.int32(0)
        exp = jnp.float32(0.0)
        for _ in range(rounds):
            ths = lo + (hi - lo) * (jnp.arange(fanout, dtype=jnp.float32) + 1.0) / fanout
            m = local[None, :] >= ths[:, None]  # [T, lam_local]
            counts = jax.lax.psum(jnp.sum(m, axis=1).astype(jnp.float32), axis)
            recsum = jax.lax.psum(
                jnp.sum(jnp.where(m, local[None, :], 0.0), axis=1), axis
            )
            ok = recsum * records_per_block >= kk
            any_ok = jnp.any(ok)
            idx = jnp.where(any_ok, jnp.argmax(jnp.where(ok, jnp.arange(fanout), -1)), 0)
            n_sel = jnp.where(any_ok, counts[idx], n_sel).astype(jnp.int32)
            exp = jnp.where(any_ok, recsum[idx] * records_per_block, exp)
            new_lo = jnp.where(any_ok, ths[idx], lo)
            new_hi = jnp.where(
                any_ok & (idx < fanout - 1), ths[jnp.minimum(idx + 1, fanout - 1)], hi
            )
            lo, hi = new_lo, jnp.where(any_ok, new_hi, ths[0])
        return lo, n_sel, exp

    fn = shard_map(
        body, mesh=mesh, in_specs=(P(axis), P()), out_specs=(P(), P(), P()),
        check_vma=False,
    )
    theta, n_sel, exp = fn(combined_global, kv)
    return ShardedBisectResult(theta=theta, num_selected=n_sel, expected_records=exp)


# ---------------------------------------------------------------------------
# Batched wave planning: one collective plans Q queries.
#
# The per-shard bodies above are pure functions of (local densities, k), so
# vmapping them over a leading query axis inside one shard_map turns the
# per-query collectives into single batched collectives (all_gather/psum have
# batching rules).  The jitted planner callables are memoized per
# (mesh, axis, static config) so a serving loop compiles once per wave-bucket
# shape, not once per wave.
# ---------------------------------------------------------------------------


class ShardedThresholdWave(NamedTuple):
    block_ids: jax.Array  # [Q, C*P] global ids, density-desc; -1 past n_sel
    num_selected: jax.Array  # [Q] int32
    expected_records: jax.Array  # [Q] f32
    sufficient: jax.Array  # [Q] bool — per query exactness flag


class ShardedTwoProngWave(NamedTuple):
    start_block: jax.Array  # [Q] int32 (group-aligned)
    end_block: jax.Array  # [Q] int32 exclusive
    expected_records: jax.Array  # [Q] f32


class ShardedBisectWave(NamedTuple):
    theta: jax.Array  # [Q] f32
    num_selected: jax.Array  # [Q] int32
    expected_records: jax.Array  # [Q] f32


@functools.lru_cache(maxsize=128)
def _threshold_wave_fn(mesh: Mesh, axis, records_per_block: int, candidates: int):
    body = partial(
        _local_threshold_body,
        records_per_block=records_per_block,
        candidates=candidates,
        axis=axis,
    )
    fn = shard_map(
        jax.vmap(body, in_axes=(0, 0)),
        mesh=mesh,
        in_specs=(P(None, axis), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_threshold_batch(
    combined_wave: jax.Array,  # [Q, lam] sharded P(None, axis)
    ks: jax.Array,  # [Q] f32
    records_per_block: int,
    mesh: Mesh,
    axis: str = "data",
    candidates: int = 64,
) -> ShardedThresholdWave:
    """Distributed THRESHOLD for a whole wave in ONE collective.

    The per-shard frontier gather of :func:`sharded_threshold` is vmapped over
    the query axis: each shard sorts its local slab once per query (batched
    argsort), contributes a ``[Q, C]`` frontier, and a single all-gather of
    ``Q·C·P·8`` bytes lets every shard compute all Q cutoffs.

    Parameters
    ----------
    combined_wave : jax.Array
        ``[Q, λ]`` combined densities, λ sharded ``P(None, axis)``.
    ks : jax.Array
        ``[Q]`` per-query record targets.
    candidates : int
        Per-shard frontier size C (must be ≤ λ/P).

    Returns
    -------
    ShardedThresholdWave
        Row q is exactly ``sharded_threshold(combined_wave[q], ks[q], ...)``:
        the vmap changes the schedule, not the arithmetic.
    """
    fn = _threshold_wave_fn(mesh, axis, records_per_block, candidates)
    ids, n_sel, exp, ok = fn(combined_wave, jnp.asarray(ks, jnp.float32))
    return ShardedThresholdWave(ids, n_sel, exp, ok)


@functools.lru_cache(maxsize=128)
def _two_prong_wave_fn(mesh: Mesh, axis, records_per_block: int, group: int):
    body = partial(
        _local_two_prong_body,
        records_per_block=records_per_block,
        group=group,
        axis=axis,
    )
    fn = shard_map(
        jax.vmap(body, in_axes=(0, 0)),
        mesh=mesh,
        in_specs=(P(None, axis), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_two_prong_batch(
    combined_wave: jax.Array,  # [Q, lam] sharded P(None, axis)
    ks: jax.Array,  # [Q] f32
    records_per_block: int,
    mesh: Mesh,
    axis: str = "data",
    group: int = 1,
) -> ShardedTwoProngWave:
    """Distributed TWO-PRONG for a whole wave in ONE collective.

    One all-gather of ``Q·(λ/G)·4`` bytes serves all Q window searches.  The
    default ``group=1`` is exact: each returned window is bit-identical to
    :func:`repro.core.two_prong.two_prong_select` on the same row, which is
    what lets :meth:`DistributedAnyK.any_k_batch` stay byte-identical to the
    host engine.  ``group>1`` trades wire bytes for ≤G-per-side window slack,
    exactly as in :func:`sharded_two_prong`.
    """
    fn = _two_prong_wave_fn(mesh, axis, records_per_block, group)
    s, e, exp = fn(combined_wave, jnp.asarray(ks, jnp.float32))
    return ShardedTwoProngWave(s, e, exp)


@functools.lru_cache(maxsize=128)
def _bisect_wave_fn(
    mesh: Mesh,
    axis,
    records_per_block: int,
    rounds: int,
    fanout: int,
    use_kernel: bool,
    interpret: bool,
):
    def body(local: jax.Array, ks: jax.Array):  # [Q, lam_local], [Q]
        if use_kernel:
            from repro.kernels.theta_stats import theta_stats_batch

        nq = local.shape[0]
        lo = jnp.zeros((nq,), jnp.float32)
        hi = jnp.full((nq,), 1.0 + 1e-6, jnp.float32)
        n_sel = jnp.zeros((nq,), jnp.int32)
        exp = jnp.zeros((nq,), jnp.float32)
        steps = (jnp.arange(fanout, dtype=jnp.float32) + 1.0) / fanout
        pos = jnp.arange(fanout, dtype=jnp.int32)

        def take(a, idx):  # [Q, T], [Q] -> [Q]
            return jnp.take_along_axis(a, idx[:, None], axis=1)[:, 0]

        for _ in range(rounds):
            ths = lo[:, None] + (hi - lo)[:, None] * steps[None, :]  # [Q, T]
            if use_kernel:
                counts, recsum = theta_stats_batch(local, ths, interpret=interpret)
            else:
                m = local[:, None, :] >= ths[:, :, None]  # [Q, T, lam_local]
                counts = jnp.sum(m, axis=2).astype(jnp.float32)
                recsum = jnp.sum(jnp.where(m, local[:, None, :], 0.0), axis=2)
            counts = jax.lax.psum(counts, axis)
            recsum = jax.lax.psum(recsum, axis)
            ok = recsum * records_per_block >= ks[:, None]
            any_ok = jnp.any(ok, axis=1)
            idx = jnp.where(
                any_ok, jnp.argmax(jnp.where(ok, pos[None, :], -1), axis=1), 0
            ).astype(jnp.int32)
            n_sel = jnp.where(any_ok, take(counts, idx), n_sel).astype(jnp.int32)
            exp = jnp.where(any_ok, take(recsum, idx) * records_per_block, exp)
            th_at = take(ths, idx)
            th_next = take(ths, jnp.minimum(idx + 1, fanout - 1))
            new_lo = jnp.where(any_ok, th_at, lo)
            new_hi = jnp.where(any_ok & (idx < fanout - 1), th_next, hi)
            lo, hi = new_lo, jnp.where(any_ok, new_hi, ths[:, 0])
        return lo, n_sel, exp

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(None, axis), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_threshold_bisect_batch(
    combined_wave: jax.Array,  # [Q, lam] sharded P(None, axis)
    ks: jax.Array,  # [Q] f32
    records_per_block: int,
    mesh: Mesh,
    axis: str | tuple[str, ...] = "data",
    rounds: int = 3,
    fanout: int = 16,
    use_kernel: bool = False,
    interpret: bool = False,
) -> ShardedBisectWave:
    """Batched distributed θ-bisection: the whole wave per psum round.

    The θ-refinement of :func:`sharded_threshold_bisect` runs for all Q
    queries at once: every round each shard computes masked ``[Q, fanout]``
    (count, Σdensity) statistics over its local blocks — with plain jnp
    reductions, or the :func:`repro.kernels.theta_stats.theta_stats_batch`
    Pallas kernel when ``use_kernel`` is set (TPU; ``interpret=True`` runs the
    kernel in interpret mode for host tests) — and ONE psum of
    ``Q·2·fanout`` floats merges the fleet.  Wire bytes per wave:
    ``rounds·Q·2·fanout·4`` B, versus ``rounds·2·fanout·4`` B *per query*
    for the scalar form.

    Returns
    -------
    ShardedBisectWave
        Per-query ``theta`` / ``num_selected`` / ``expected_records``; a
        statistics planner (no materialized ids) — use the gather planner when
        block ids are needed.
    """
    fn = _bisect_wave_fn(
        mesh, axis, records_per_block, rounds, fanout, use_kernel, interpret
    )
    theta, n_sel, exp = fn(combined_wave, jnp.asarray(ks, jnp.float32))
    return ShardedBisectWave(theta=theta, num_selected=n_sel, expected_records=exp)


def _next_pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


@functools.lru_cache(maxsize=64)
def _sharded_device_round_fn(
    mesh: Mesh, axis, records_per_block: int, lam: int, num_shards: int, group: int
):
    """Jitted mesh-native round body for the device-resident wave pipeline.

    The sharded analogue of ``repro.core.multi_query._local_round_fn``: one
    round = replay last round's host choices onto the device exclusion mask,
    plan the whole wave with ONE ``shard_map`` collective per planner, and
    feed the collective outputs *directly* into the device block-cut — the
    THRESHOLD prefixes are scattered into the ``[Q, λ]`` plan mask on device,
    never re-materialized as host id lists between plan and cut.  The
    frontier is the full local sort (``C = λ/P``), exact by construction, so
    no sufficiency check (and no extra device→host transfer) is needed.
    """
    from repro.kernels.plan_wave import apply_chosen, pack_plan

    pad = (-lam) % num_shards
    lam_p = lam + pad
    lam_local = lam_p // num_shards
    th_fn = _threshold_wave_fn(mesh, axis, records_per_block, lam_local)
    tp_fn = _two_prong_wave_fn(mesh, axis, records_per_block, group)

    def round_fn(combined0, excl, th_prev, tp_prev, chosen_prev, needs):
        excl = apply_chosen(excl, th_prev, tp_prev, chosen_prev)
        masked = jnp.where(excl, jnp.float32(0.0), combined0)
        wave = jnp.pad(masked, ((0, 0), (0, pad)))  # λ to a shard multiple
        ids, n_sel, _exp, _ok = th_fn(wave, needs)
        # device cut: scatter the selected prefix (ids are -1 past n_sel;
        # scatter-add cannot collide because selected ids are unique per row)
        qa = combined0.shape[0]
        pos = jnp.arange(ids.shape[1], dtype=jnp.int32)
        selv = (pos[None, :] < n_sel[:, None]) & (ids >= 0)
        hits = (
            jnp.zeros((qa, lam_p), jnp.int32)
            .at[jnp.arange(qa)[:, None], jnp.maximum(ids, 0)]
            .add(selv.astype(jnp.int32))
        )
        th_mask = (hits > 0)[:, :lam]
        s, e, _ = tp_fn(wave, needs)
        s = s.astype(jnp.int32)
        e = jnp.minimum(e, lam).astype(jnp.int32)  # λ-padding never planned
        packed = pack_plan(th_mask, n_sel, s, e)
        return packed, excl, th_mask, jnp.stack([s, e], axis=1)

    return jax.jit(round_fn)


class DistributedAnyK:
    """Production wrapper over the SPMD planners.

    Handles geometric candidate refill on an insufficient THRESHOLD frontier,
    planner selection by shard count (sort-gather below ``bisect_above``
    shards, θ-bisection beyond — the wire crossover measured in EXPERIMENTS.md
    §Perf HC-C iter 4), wave-level batched planning, and fetches routed
    through the engine-lifetime block LRU.

    Parameters
    ----------
    mesh : jax.sharding.Mesh
        Mesh whose ``axis`` dimension shards the λ block range.
    axis : str | tuple[str, ...]
        Mesh axis (or axes) the density maps are sharded over.
    records_per_block : int
        Block capacity R of the store being planned for.
    candidates : int
        Initial per-shard THRESHOLD frontier size C (doubled on refill).
    max_refills : int
        Scalar-path cap on frontier refills (the wave path instead grows C
        until every query is provably exact or C reaches λ/P, which is
        always exact).
    bisect_above : int
        Shard count beyond which the scalar path switches from the
        sort-gather planner to θ-bisection.
    block_cache : repro.core.block_cache.BlockLRUCache | None
        Engine-lifetime LRU shared with the host paths; pass
        ``NeedleTailEngine.block_cache`` (or use
        :meth:`repro.core.engine.NeedleTailEngine.attach_mesh`, which wires
        it for you) so scalar, batched, and sharded fetches share one cache.
    two_prong_group : int
        G for the wave TWO-PRONG; the default 1 is exact (byte-identity).
    peer_group : repro.storage.peer.PeerGroup | None
        Cooperative peer-memory cluster; arms :meth:`fetch_remote` so block
        requests are answered from other shards' resident host tiers over
        the ``ici`` hop before falling through to the backing store.
    """

    def __init__(self, mesh: Mesh, axis="data", records_per_block: int = 8192,
                 candidates: int = 16, max_refills: int = 4,
                 bisect_above: int = 512, block_cache=None,
                 two_prong_group: int = 1, remote_cost=None,
                 peer_group=None):
        from repro.core.cost_model import make_cost_model

        self.mesh = mesh
        self.axis = axis
        self.rpb = records_per_block
        self.candidates = candidates
        self.max_refills = max_refills
        # optional engine-lifetime cache (a flat
        # repro.core.block_cache.BlockLRUCache or a tiered
        # repro.storage.TierStack — both expose the same get_many surface);
        # pass NeedleTailEngine.block_cache to share one cache across the
        # scalar, batched, and sharded fetch paths
        self.block_cache = block_cache
        self.two_prong_group = two_prong_group
        # cost model pricing a NON-resident block of a sharded plan: fetching
        # it means crossing the interconnect to the shard that owns it, so
        # the `ici` preset is the default.  fetch_plan records the modeled
        # cost of each fetch in `last_fetch_io_s` (residency-aware when a
        # TierStack is attached: resident blocks are priced by their tier).
        # `price_fetches=False` skips the diagnostic on latency-critical
        # paths (the pricing walks the plan's residency before each fetch).
        self.remote_cost = remote_cost or make_cost_model("ici")
        self.price_fetches = True
        self.last_fetch_io_s = 0.0
        # cooperative peer-memory tier (repro.storage.peer.PeerGroup): when
        # set, fetch_remote answers block requests from other shards'
        # resident host tiers — attach_mesh routes the engine stack's
        # PeerTier through it so cross-shard reads go through the planner
        self.peer_group = peer_group
        sz = 1
        for a in (axis if isinstance(axis, tuple) else (axis,)):
            sz *= mesh.shape[a]
        self.num_shards = sz
        self.use_bisect = sz > bisect_above

    # ------------------------------------------------------------- wave shard
    def _device_wave(self, combined: np.ndarray) -> tuple[jax.Array, int]:
        """Pad λ to a shard multiple (zero density: never planned) and place
        the ``[Q, λ']`` wave with ``P(None, axis)``.  Returns (array, λ)."""
        combined = np.ascontiguousarray(np.asarray(combined, dtype=np.float32))
        qa, lam = combined.shape
        pad = (-lam) % self.num_shards
        if pad:
            combined = np.pad(combined, ((0, 0), (0, pad)))
        sharded = jax.device_put(
            jnp.asarray(combined), NamedSharding(self.mesh, P(None, self.axis))
        )
        return sharded, lam

    @staticmethod
    def plan_block_ids(plan) -> "np.ndarray":
        """Materialize a sharded plan's block ids on the host (§4.1 ascending
        fetch order)."""
        if isinstance(plan, ShardedThresholdResult):
            ids = np.asarray(plan.block_ids)[: int(plan.num_selected)]
            return np.sort(ids.astype(np.int64))
        if isinstance(plan, ShardedTwoProngResult):
            return np.arange(int(plan.start_block), int(plan.end_block), dtype=np.int64)
        raise TypeError(f"cannot materialize block ids from {type(plan).__name__}")

    def fetch_remote(self, block_ids, requester: int | None = 0) -> dict:
        """Answer block requests from the peer group's resident host tiers
        (the remote side of the cooperative peer-memory tier,
        ``repro.storage.peer``).

        Parameters
        ----------
        block_ids : array-like of int
            Blocks the requesting shard wants.
        requester : int | None
            Requesting shard id — its own host tier is excluded (a shard
            never answers itself over the interconnect).

        Returns
        -------
        dict
            ``block_id -> (dims, meas, valid, nbytes)`` host slabs for every
            id some peer's host tier could serve.  Ids absent from the dict
            mean no shard holds the block (or its in-flight read was
            invalidated by an append) — callers fall through to the backing
            store.  ``{}`` when no peer group is attached.  A peer that is
            down in ``"raise"`` mode propagates :class:`repro.storage.peer.
            PeerUnavailable`; the requesting ``PeerTier`` catches it and
            falls through.
        """
        if self.peer_group is None:
            return {}
        out: dict[int, tuple] = {}
        for b in np.asarray(block_ids, dtype=np.int64).ravel():
            slab = self.peer_group.fetch_block(int(b), requester=requester)
            if slab is not None:
                out[int(b)] = slab
        return out

    def fetch_plan(self, store, plan):
        """Fetch a sharded plan's blocks through the shared engine-lifetime
        LRU when one is attached (``block_cache``), else straight from the
        store.

        Parameters
        ----------
        store : repro.data.block_store.BlockStore
            The store the plan refers to.
        plan : ShardedThresholdResult | ShardedTwoProngResult
            A scalar sharded plan (wave plans hand out per-query id arrays
            directly; see :meth:`threshold_plan_wave`).

        Returns
        -------
        tuple
            ``(block_ids, dims, measures, valid)`` — slabs byte-identical to
            ``store.fetch(block_ids)`` (the LRU's byte-identity guarantee).

        Notes
        -----
        ``last_fetch_io_s`` records this fetch's modeled I/O under the
        ``ici`` remote-shard pricing (``remote_cost``): a non-resident block
        crosses the interconnect.  With a :class:`repro.storage.TierStack`
        attached the price is residency-aware — locally resident blocks are
        priced by their tier's model, only true remote reads by ``ici``.
        """
        ids = self.plan_block_ids(plan)
        if getattr(self, "price_fetches", True):
            # priced BEFORE the fetch: residency must reflect what this
            # fetch will actually cross the interconnect for
            eff = getattr(self.block_cache, "effective_io_time", None)
            if eff is not None:
                self.last_fetch_io_s = eff(ids, backing=self.remote_cost)
            else:
                self.last_fetch_io_s = self.remote_cost.io_time(ids)
        if self.block_cache is not None:
            return (ids, *self.block_cache.get_many(store, ids))
        return (ids, *store.fetch(ids))

    def threshold_plan(self, combined_global: jax.Array, k: float):
        """Scalar THRESHOLD plan with geometric frontier refill.

        Uses θ-bisection beyond ``bisect_above`` shards (statistics only),
        the sort-gather planner otherwise; on an insufficient frontier the
        candidate count doubles, up to ``max_refills`` times.
        """
        if self.use_bisect:
            return sharded_threshold_bisect(
                combined_global, k, self.rpb, self.mesh, self.axis
            )
        c = self.candidates
        for _ in range(self.max_refills):
            r = sharded_threshold(
                combined_global, k, self.rpb, self.mesh, self.axis, candidates=c
            )
            if bool(r.sufficient):
                return r
            c *= 2  # geometric backoff: some shard's frontier was exhausted
        return r

    def two_prong_plan(self, combined_global: jax.Array, k: float, group: int = 64):
        """Scalar TWO-PRONG plan at G-block granularity (see
        :func:`sharded_two_prong`)."""
        return sharded_two_prong(
            combined_global, k, self.rpb, self.mesh, self.axis, group=group
        )

    # ----------------------------------------------------------- wave planning
    def threshold_plan_wave(
        self, combined: np.ndarray, needs: np.ndarray
    ) -> list[np.ndarray]:
        """THRESHOLD-plan a whole wave with one collective per refill round.

        Parameters
        ----------
        combined : numpy.ndarray
            ``[Q, λ]`` combined densities (host mirror; exclusions already
            zeroed in).
        needs : numpy.ndarray
            ``[Q]`` per-query record targets.

        Returns
        -------
        list[numpy.ndarray]
            Per-query ascending block-id arrays, each byte-identical (as a
            set, and therefore after the engine's ascending §4.1 fetch sort)
            to the host planner's selection.  Exactness is guaranteed: the
            frontier doubles until every query's ``sufficient`` flag is set,
            and a frontier of λ/P (the full local sort) is exact by
            construction.
        """
        combined = np.ascontiguousarray(np.asarray(combined, dtype=np.float32))
        needs = np.asarray(needs, dtype=np.float32)
        qa = combined.shape[0]
        qb = _next_pow2(max(qa, 1))
        comb_pad = np.zeros((qb, combined.shape[1]), np.float32)
        comb_pad[:qa] = combined
        k_pad = np.ones((qb,), np.float32)
        k_pad[:qa] = needs
        wave, lam = self._device_wave(comb_pad)
        lam_local = wave.shape[1] // self.num_shards
        c = min(self.candidates, lam_local)
        while True:
            r = sharded_threshold_batch(
                wave, k_pad, self.rpb, self.mesh, self.axis, candidates=c
            )
            # a full local sort (C == λ/P) is exact even when the flag is
            # pessimistic (a shard whose entire range is selected saturates it)
            if c == lam_local or bool(np.asarray(r.sufficient)[:qa].all()):
                break
            c = min(c * 2, lam_local)
        ids = np.asarray(r.block_ids)
        n_sel = np.asarray(r.num_selected)
        return [
            np.sort(ids[q, : int(n_sel[q])].astype(np.int64)) for q in range(qa)
        ]

    def two_prong_plan_wave(
        self, combined: np.ndarray, needs: np.ndarray
    ) -> list[tuple[int, int]]:
        """TWO-PRONG-plan a whole wave with one collective.

        Returns per-query ``(start, end)`` windows (end clamped to the true λ:
        the λ-padding blocks added for shard divisibility carry zero density
        and the host reference never selects past λ).  With the default
        ``two_prong_group=1`` each window is bit-identical to
        :func:`repro.core.two_prong.two_prong_select` on the same row.
        """
        combined = np.ascontiguousarray(np.asarray(combined, dtype=np.float32))
        needs = np.asarray(needs, dtype=np.float32)
        qa = combined.shape[0]
        qb = _next_pow2(max(qa, 1))
        comb_pad = np.zeros((qb, combined.shape[1]), np.float32)
        comb_pad[:qa] = combined
        k_pad = np.ones((qb,), np.float32)
        k_pad[:qa] = needs
        wave, lam = self._device_wave(comb_pad)
        r = sharded_two_prong_batch(
            wave, k_pad, self.rpb, self.mesh, self.axis,
            group=self.two_prong_group,
        )
        starts = np.asarray(r.start_block)
        ends = np.asarray(r.end_block)
        return [
            (int(starts[q]), min(int(ends[q]), lam)) for q in range(qa)
        ]

    def device_round_fn(self, lam: int, records_per_block: int | None = None):
        """Memoized jitted round body for the device-resident pipeline.

        Used by ``repro.core.multi_query._device_plan_loop`` when this
        planner is attached: each refill round's combine-masked wave is
        planned by ONE ``shard_map`` collective (full-local-sort THRESHOLD —
        exact, no frontier refill — plus the wave TWO-PRONG) whose outputs
        feed the device block-cut directly; the round returns the packed
        single-transfer plan matrix.  Byte-identity with the host oracle
        holds for ``two_prong_group == 1`` (the serving default; larger
        groups give group-aligned approximate windows, exactly as on the
        host-mirror sharded path).

        Parameters
        ----------
        lam : int
            True (unpadded) block count λ of the store being planned.
        records_per_block : int | None
            Block capacity; defaults to this planner's ``rpb``.
        """
        return _sharded_device_round_fn(
            self.mesh, self.axis, records_per_block or self.rpb, lam,
            self.num_shards, self.two_prong_group,
        )

    def bisect_stats_wave(
        self, combined: np.ndarray, needs: np.ndarray, **kw
    ) -> ShardedBisectWave:
        """Batched θ-bisection statistics for a wave (no materialized ids);
        forwards ``rounds`` / ``fanout`` / ``use_kernel`` / ``interpret`` to
        :func:`sharded_threshold_bisect_batch`."""
        combined = np.ascontiguousarray(np.asarray(combined, dtype=np.float32))
        needs = np.asarray(needs, dtype=np.float32)
        wave, _ = self._device_wave(combined)
        return sharded_threshold_bisect_batch(
            wave, needs, self.rpb, self.mesh, self.axis, **kw
        )

    def any_k_batch(self, engine, queries, algo: str = "auto", device: bool = False):
        """Evaluate Q any-k queries with sharded batched planning.

        The mesh-native form of
        :meth:`repro.core.engine.NeedleTailEngine.any_k_batch`: each refill
        round's plan wave runs as ONE ``shard_map`` collective
        (:func:`sharded_threshold_batch` / :func:`sharded_two_prong_batch`)
        instead of Q host-mirror planner calls, and the resulting deduplicated
        fetches go through the engine-lifetime block LRU.  Per-query results
        are byte-identical to the host path (and therefore to Q sequential
        ``engine.any_k`` calls).

        Parameters
        ----------
        engine : repro.core.engine.NeedleTailEngine
            The engine owning the store, cost model, and caches.
        queries : Sequence[BatchQuery | tuple]
            As accepted by :func:`repro.core.multi_query.run_batch`.
        algo : str
            ``"threshold"`` / ``"two_prong"`` / ``"auto"`` run sharded;
            ``"forward_optimal"`` is inherently sequential and falls back to
            the host planner.
        device : bool
            ``True`` runs the device-resident pipeline: the wave state stays
            on device across refill rounds and each round's collective feeds
            the device block-cut directly (:meth:`device_round_fn`), with ONE
            packed device→host transfer per round.

        Returns
        -------
        repro.core.multi_query.BatchQueryResult
        """
        from repro.core.multi_query import run_batch

        return run_batch(
            engine, queries, algo=algo, planner=self, plan_on_host=not device
        )
