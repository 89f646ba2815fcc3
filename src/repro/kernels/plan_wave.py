"""Fused device-resident wave planning: combine → θ-stats → sort → cut.

The batched engine used to bounce every refill round through host mirrors:
host combine, ``np.asarray`` of the sorted orders, host prefix cuts, host
window diffs — at serving scale the host↔device transfers dominate the very
path the paper optimizes.  :func:`plan_wave` chains the batched kernels so one
device program turns a wave's ``[Q, λ]`` densities + exclusion masks + needs
into final per-query block plans:

1. **combine** — :func:`repro.kernels.density_combine.density_combine_batch`
   (Pallas) or the bit-exact jnp left fold (:func:`combine_wave`), producing
   the ``[Q, λ]`` ⊕-combined matrix.
2. **sort + cut** — :func:`repro.core.threshold.threshold_sort_batch` over the
   exclusion-masked rows, then a vectorized prefix cut that is bit-identical
   to :func:`repro.core.threshold.threshold_cut` per row.  The cut is
   materialized as a ``[Q, λ]`` selection mask (ascending §4.1 order is a
   host-side ``np.flatnonzero``), not an id list — fixed shape, jit-safe.
3. **θ-stats** — :func:`repro.kernels.theta_stats.theta_stats_batch` (Pallas)
   or its jnp oracle, evaluated at each query's cut threshold θ_q: the §4.1
   running-threshold invariant (#blocks clearing θ_q ≥ n_sel, expected
   records ≥ need when reachable) is verified *on device* and the expected
   record mass is reported per query.
4. **window** — :func:`repro.core.two_prong.two_prong_select_batch` minimal
   windows for the TWO-PRONG / auto paths.

:func:`pack_plan` flattens the whole result into ONE ``int32 [Q, λ+3]``
matrix so the host consumes a refill round in a single device→host transfer
(:func:`unpack_plan` is the host-side inverse); :func:`apply_chosen` replays
the host's per-query algo choice onto the device-resident exclusion mask, so
the next round plans against up-to-date exclusions without re-uploading them.

:func:`block_gather` materializes the deduplicated block union of a wave from
the device-resident lane-dense ``[λ, ·, R]`` store slabs in one gather launch — the
scalar-prefetched block ids drive the input ``index_map`` exactly like the
predicate-row gather in :mod:`repro.kernels.density_combine`.

Pure-jnp oracles live in :mod:`repro.kernels.ref` (``plan_wave_ref``,
``block_gather_ref``).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.threshold import threshold_sort_batch
from repro.core.two_prong import two_prong_select_batch
from repro.kernels.density_combine import _combine_local, density_combine_batch

THETA_FANOUT = 8  # θ-stats candidate count (kernel wants a multiple of 8)


class PlanWaveResult(NamedTuple):
    """One wave's device-resident plans (all arrays stay on device)."""

    combined: jax.Array  # [Q, λ] f32 exclusion-masked combined densities
    th_mask: jax.Array  # [Q, λ] bool THRESHOLD selection (the prefix cut)
    n_sel: jax.Array  # [Q] i32 prefix length (the planned-prefix cursor)
    theta: jax.Array  # [Q] f32 cut threshold (density of the last selected)
    theta_count: jax.Array  # [Q] f32 #blocks clearing θ_q (≥ n_sel: ties)
    expected_records: jax.Array  # [Q] f32 record mass clearing θ_q (§4.1 τ)
    tp_start: jax.Array  # [Q] i32 TWO-PRONG window start (inclusive)
    tp_end: jax.Array  # [Q] i32 TWO-PRONG window end (exclusive)


def combine_wave(
    densities: jax.Array,  # [rows, λ] f32
    row_matrix: jax.Array,  # [Q, γ_max] int32, padded with -1
    op: str = "and",
    use_kernel: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """``[Q, λ]`` ⊕-combined wave matrix, bit-identical per row to the host
    :func:`repro.core.density_map.combine_densities_batch_np` combine.

    The default is the sequential jnp left fold over γ (the same reduction
    order as the host combine, so the bytes match exactly — the byte-identity
    contract of the device pipeline rests on this); ``use_kernel`` routes the
    :func:`repro.kernels.density_combine.density_combine_batch` Pallas kernel
    instead (TPU; accumulation order identical, pair with allclose tests).
    """
    if use_kernel:
        return density_combine_batch(densities, row_matrix, op, interpret=interpret)
    return _combine_local(densities, row_matrix.astype(jnp.int32), op)


def _cut_batch(sorted_d: jax.Array, cum: jax.Array, needs: jax.Array, rpb: int):
    """Vectorized prefix cut, bit-identical per row to
    :func:`repro.core.threshold.threshold_cut` (same f32 ops, same argmax)."""
    cum_records = cum * jnp.float32(rpb)
    reached = cum_records >= needs[:, None]
    any_hit = jnp.any(reached, axis=1)
    first = jnp.argmax(reached, axis=1)
    nonzero = jnp.sum(sorted_d > 0.0, axis=1)
    return jnp.where(any_hit, first + 1, nonzero).astype(jnp.int32)


def plan_wave_from_combined(
    combined0: jax.Array,  # [Q, λ] f32 base combined densities (no exclusions)
    excl: jax.Array,  # [Q, λ] bool blocks already planned/fetched per query
    needs: jax.Array,  # [Q] f32 per-query record targets
    records_per_block: int,
    use_kernel: bool = False,
    interpret: bool = False,
) -> PlanWaveResult:
    """Plan one refill round on device from an already-combined wave matrix.

    Round 0 of the device pipeline computes ``combined0`` once (via
    :func:`combine_wave`); every later round reuses it and only the exclusion
    mask changes — this function is the per-round body.
    """
    qa, lam = combined0.shape
    if lam == 0:  # degenerate λ=0 store: nothing to plan (argmax-safe)
        zi = jnp.zeros((qa,), jnp.int32)
        zf = jnp.zeros((qa,), jnp.float32)
        return PlanWaveResult(
            combined=combined0, th_mask=jnp.zeros((qa, 0), bool), n_sel=zi,
            theta=zf, theta_count=zf, expected_records=zf, tp_start=zi, tp_end=zi,
        )
    masked = jnp.where(excl, jnp.float32(0.0), combined0)
    si, sd, cum = threshold_sort_batch(masked)
    n_sel = _cut_batch(sd, cum, needs, records_per_block)
    # materialize the prefix as a [Q, λ] mask: rank[si[q, j]] = j < n_sel[q].
    # si is a permutation per row, so a scatter-set cannot collide.
    sel_sorted = jnp.arange(lam, dtype=jnp.int32)[None, :] < n_sel[:, None]
    th_mask = (
        jnp.zeros((qa, lam), bool)
        .at[jnp.arange(qa)[:, None], si]
        .set(sel_sorted)
    )
    # θ-stats at the cut threshold: the running-threshold invariant, on device
    theta = jnp.where(
        n_sel > 0,
        jnp.take_along_axis(sd, jnp.maximum(n_sel - 1, 0)[:, None], axis=1)[:, 0],
        jnp.float32(0.0),
    )
    steps = 1.0 + jnp.arange(THETA_FANOUT, dtype=jnp.float32)  # θ, 2θ, 3θ, ...
    thetas = theta[:, None] * steps[None, :]
    if use_kernel:
        from repro.kernels.theta_stats import theta_stats_batch

        counts, recsum = theta_stats_batch(masked, thetas, interpret=interpret)
    else:
        from repro.kernels.ref import theta_stats_batch_ref

        counts, recsum = theta_stats_batch_ref(masked, thetas)
    has_cut = n_sel > 0
    theta_count = jnp.where(has_cut, counts[:, 0], jnp.float32(0.0))
    expected = jnp.where(
        has_cut, recsum[:, 0] * jnp.float32(records_per_block), jnp.float32(0.0)
    )
    tp = two_prong_select_batch(masked, needs, records_per_block)
    return PlanWaveResult(
        combined=masked,
        th_mask=th_mask,
        n_sel=n_sel,
        theta=theta,
        theta_count=theta_count,
        expected_records=expected,
        tp_start=tp.start.astype(jnp.int32),
        tp_end=tp.end.astype(jnp.int32),
    )


def plan_wave(
    densities: jax.Array,  # [rows, λ] f32 density tensor (device-resident)
    row_matrix: jax.Array,  # [Q, γ_max] int32, padded with -1
    excl: jax.Array,  # [Q, λ] bool
    needs: jax.Array,  # [Q] f32
    records_per_block: int,
    op: str = "and",
    use_kernel: bool = False,
    interpret: bool = False,
) -> PlanWaveResult:
    """Fused combine → θ-stats → sort → cut for one wave, fully on device.

    The single-shot form (round 0 of the pipeline): chains
    :func:`combine_wave` into :func:`plan_wave_from_combined`.  Oracle:
    :func:`repro.kernels.ref.plan_wave_ref`.
    """
    combined0 = combine_wave(
        densities, row_matrix, op, use_kernel=use_kernel, interpret=interpret
    )
    return plan_wave_from_combined(
        combined0, excl, needs, records_per_block,
        use_kernel=use_kernel, interpret=interpret,
    )


# --------------------------------------------------------------------------
# One-transfer round protocol: pack on device, unpack on host.
# --------------------------------------------------------------------------

def pack_plan(
    th_mask: jax.Array,  # [Q, λ] bool
    n_sel: jax.Array,  # [Q] i32
    tp_start: jax.Array,  # [Q] i32
    tp_end: jax.Array,  # [Q] i32
) -> jax.Array:
    """Flatten a wave's plans into ONE ``int32 [Q, λ+3]`` matrix.

    Columns ``[0:λ)`` are the THRESHOLD selection mask, column λ the prefix
    cursor ``n_sel``, columns λ+1/λ+2 the TWO-PRONG window.  One
    ``np.asarray`` of this matrix is the round's entire device→host traffic
    (both the local and the sharded device rounds emit this format).
    """
    return jnp.concatenate(
        [
            th_mask.astype(jnp.int32),
            n_sel.astype(jnp.int32)[:, None],
            tp_start.astype(jnp.int32)[:, None],
            tp_end.astype(jnp.int32)[:, None],
        ],
        axis=1,
    )


def unpack_plan(packed: np.ndarray, lam: int):
    """Host-side inverse of :func:`pack_plan`.

    Returns ``(th_mask [Q, λ] bool, n_sel [Q], tp_start [Q], tp_end [Q])``;
    a query's ascending §4.1 THRESHOLD plan is ``np.flatnonzero(th_mask[q])``.
    """
    packed = np.asarray(packed)
    return (
        packed[:, :lam].astype(bool),
        packed[:, lam],
        packed[:, lam + 1],
        packed[:, lam + 2],
    )


def apply_chosen(
    excl: jax.Array,  # [Q, λ] bool
    th_mask_prev: jax.Array,  # [Q, λ] bool previous round's THRESHOLD mask
    tp_prev: jax.Array,  # [Q, 2] i32 previous round's TWO-PRONG window
    chosen_prev: jax.Array,  # [Q] i8: 0=threshold, 1=two_prong, -1=no-op
) -> jax.Array:
    """Replay the host's per-query algo choice onto the exclusion mask.

    The host picks each query's plan (threshold prefix, two-prong window, or
    the §7.2 cost-compared winner) from the packed transfer; next round it
    uploads only the ``[Q]`` choice codes and the device reconstructs the
    fetched block set from its own carried cursors — bit-identical to the
    host's ``np.setdiff1d(plan, exclude)`` because the window diff is
    ``window & ~excl`` and threshold prefixes never overlap exclusions
    (excluded blocks are zero-density and the cut never selects them).
    """
    lam = excl.shape[1]
    pos = jnp.arange(lam, dtype=jnp.int32)[None, :]
    win = (pos >= tp_prev[:, :1]) & (pos < tp_prev[:, 1:2])
    new = jnp.where(
        (chosen_prev == 0)[:, None],
        th_mask_prev,
        jnp.where((chosen_prev == 1)[:, None], win & ~excl, False),
    )
    return excl | new


def join_wave_slots(
    combined0: jax.Array,  # [Qb, λ] f32 base combined densities
    excl: jax.Array,  # [Qb, λ] bool
    th_mask: jax.Array,  # [Qb, λ] bool previous round's THRESHOLD mask
    tp_win: jax.Array,  # [Qb, 2] i32 previous round's TWO-PRONG window
    idx: jax.Array,  # [J] i32 slot rows being (re)occupied
    rows: jax.Array,  # [J, λ] f32 joiners' base combined densities
    excl_rows: jax.Array,  # [J, λ] bool joiners' prior exclusions
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Admit joining queries into slot rows of a device-resident wave.

    The continuous serving loop grows and shrinks the wave's Q axis without
    reallocating device state: a departure only clears the host-side active
    mask and choice code (a row whose code is -1 is never replayed by
    :func:`apply_chosen`, and its plan outputs are simply not decoded), while
    a join scatters the newcomer's base combined row and prior-exclusion row
    into the fixed ``[Qb, λ]`` state and zeroes the stale prefix cursors left
    by the previous occupant.  Rows are planned independently, so active
    occupants' plans are bit-identical whatever the other rows hold, and the
    one-packed-transfer-per-round protocol is untouched — joins are pure
    device-side scatters.
    """
    combined0 = combined0.at[idx].set(rows)
    excl = excl.at[idx].set(excl_rows)
    th_mask = th_mask.at[idx].set(False)
    tp_win = tp_win.at[idx].set(0)
    return combined0, excl, th_mask, tp_win


# --------------------------------------------------------------------------
# block_gather: the wave's deduplicated union in one device gather.
# --------------------------------------------------------------------------

def _gather_kernel(ids_ref, src_ref, out_ref):
    del ids_ref  # consumed by the index_map (scalar prefetch)
    out_ref[...] = src_ref[...]


def block_gather(
    slab: jax.Array,  # [λ, d, R] (or [λ, R]) block-major, lane-dense store tensor
    block_ids: jax.Array,  # [U] int32 union ids
    interpret: bool = False,
) -> jax.Array:
    """Gather ``slab[block_ids]`` in one Pallas launch: ``[U, d, R]``.

    The scalar-prefetched ids drive the input ``index_map``, so each union
    block streams HBM→VMEM exactly once and the gather itself costs nothing —
    the device-resident form of the §4.1 "fetch every planned block once"
    union fetch.  The record axis R is minor (the store's lane-dense layout),
    so a block's ``(d, R)`` tile is the array's own last two dims, which the
    TPU lowering accepts for any d; a 2-D ``[λ, R]`` slab travels as
    ``[λ, 1, R]``.  Oracle: :func:`repro.kernels.ref.block_gather_ref`.
    """
    squeeze = slab.ndim == 2
    if squeeze:
        slab = slab[:, None, :]
    lam, d, r = slab.shape
    u = block_ids.shape[0]
    if u == 0 or lam == 0:
        out = jnp.zeros((u, d, r), slab.dtype)
        return out[:, 0, :] if squeeze else out

    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(u,),
            in_specs=[
                pl.BlockSpec((1, d, r), lambda i, ids: (ids[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, d, r), lambda i, ids: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((u, d, r), slab.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(block_ids.astype(jnp.int32), slab)
    return out[:, 0, :] if squeeze else out

