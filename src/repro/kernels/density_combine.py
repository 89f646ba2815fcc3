"""Fused predicate-row gather + ⊕-combine Pallas kernel (paper §3.2).

The query-time hot loop of NeedleTail is ``⊕_{j=1..γ} S_j[b]`` over all λ blocks.
A naive implementation gathers γ rows of the ``[rows, λ]`` density tensor to HBM
and then combines them — 2γ·λ·4 bytes of HBM traffic.  This kernel streams each
predicate row tile HBM→VMEM exactly once and combines in-register: (γ+1)·λ·4
bytes, the minimum possible.

Grid: ``(λ_tiles, γ)`` with the predicate axis innermost, so each output tile is
revisited γ consecutive steps (TPU-legal accumulation).  The row ids are scalar-
prefetched and drive the input ``index_map`` — the gather costs nothing.

:func:`density_combine_batch` is the multi-query form: a ``[Q, γ_max]`` row
matrix (padded with -1) produces the full ``[Q, λ]`` combined-density matrix in
one launch — grid ``(Q, λ_tiles, γ_max)``.  Padded positions read row 0 but
contribute the ⊕-identity, so ragged batches combine exactly.

:func:`density_combine_batch_sharded` is the mesh-native wave form: the
``[rows, λ]`` density tensor stays sharded over the mesh ``data`` axis (each
shard owns a contiguous λ/P block range, see :mod:`repro.core.sharded`) and
every shard combines its local slab for ALL Q queries at once — no collective
at all, because ⊕ is elementwise over λ.  The result is the ``[Q, λ]``
combined matrix already laid out ``P(None, axis)``, exactly the operand shape
the batched sharded planners consume.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANE_TILE = 512  # λ-tile; multiple of the 128-lane VPU width


def _kernel(rows_ref, dens_ref, out_ref, *, op: str, gamma: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, 1.0 if op == "and" else 0.0)

    tile = dens_ref[0]  # [1, LANE_TILE]
    if op == "and":
        out_ref[...] *= tile
    else:
        out_ref[...] += tile

    if op == "or":

        @pl.when(j == gamma - 1)
        def _clip():
            out_ref[...] = jnp.minimum(out_ref[...], 1.0)


def _unit_rows(densities: jax.Array, lam_p: int) -> jax.Array:
    """``[rows, λ]`` -> ``[rows, 1, λ_p]`` (zero-padded to the lane tile).

    A gathered row gets its own unit axis so each block's last two dims are
    ``(1, LANE_TILE)``: the TPU lowering accepts a second-minor block dim of
    1 only when it equals the array's, which a ``(1, LANE_TILE)`` block over
    the 2-D ``[rows, λ]`` tensor does not.
    """
    rows, lam = densities.shape
    if lam_p != lam:
        densities = jnp.pad(densities, ((0, 0), (0, lam_p - lam)))
    return densities.reshape(rows, 1, lam_p)


def density_combine(
    densities: jax.Array,  # [rows, lam] f32
    row_ids: jax.Array,  # [gamma] int32
    op: str = "and",
    interpret: bool = False,
) -> jax.Array:
    """Returns the combined per-block density vector ``[lam]``."""
    rows, lam = densities.shape
    gamma = row_ids.shape[0]
    lam_p = lam + (-lam) % LANE_TILE
    grid = (lam_p // LANE_TILE, gamma)

    out = pl.pallas_call(
        functools.partial(_kernel, op=op, gamma=gamma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, 1, LANE_TILE), lambda i, j, rows: (rows[j], 0, i)
                ),
            ],
            out_specs=pl.BlockSpec((1, LANE_TILE), lambda i, j, rows: (0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((1, lam_p), densities.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
    )(row_ids.astype(jnp.int32), _unit_rows(densities, lam_p))
    return out[0, :lam]


def _batch_kernel(rows_ref, dens_ref, out_ref, *, op: str, gamma: int):
    q = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, 1.0 if op == "and" else 0.0)

    tile = dens_ref[...]  # [1, 1, LANE_TILE]
    # padded row slots (-1) contribute the ⊕-identity; the index_map clamped
    # their gather to row 0, so mask the loaded tile out here
    valid = rows_ref[q, j] >= 0
    if op == "and":
        out_ref[...] *= jnp.where(valid, tile, 1.0)
    else:
        out_ref[...] += jnp.where(valid, tile, 0.0)

    if op == "or":

        @pl.when(j == gamma - 1)
        def _clip():
            out_ref[...] = jnp.minimum(out_ref[...], 1.0)


def density_combine_batch(
    densities: jax.Array,  # [rows, lam] f32
    row_matrix: jax.Array,  # [Q, gamma_max] int32, padded with -1
    op: str = "and",
    interpret: bool = False,
) -> jax.Array:
    """Returns the combined per-block density matrix ``[Q, lam]``.

    One device pass serves all Q queries: each predicate-row tile streams
    HBM→VMEM once per referencing query and ⊕-combines in-register into that
    query's output tile.  The query axis is outermost (parallel-safe); the
    predicate axis stays innermost so each output tile is revisited γ_max
    consecutive steps, exactly like the single-query kernel.
    """
    rows, lam = densities.shape
    nq, gamma = row_matrix.shape
    lam_p = lam + (-lam) % LANE_TILE
    grid = (nq, lam_p // LANE_TILE, gamma)

    out = pl.pallas_call(
        functools.partial(_batch_kernel, op=op, gamma=gamma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, 1, LANE_TILE),
                    lambda q, i, j, rows: (jnp.maximum(rows[q, j], 0), 0, i),
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, LANE_TILE), lambda q, i, j, rows: (q, 0, i)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((nq, 1, lam_p), densities.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")
        ),
    )(row_matrix.astype(jnp.int32), _unit_rows(densities, lam_p))
    return out[:, 0, :lam]


def _combine_local(dens_local: jax.Array, row_matrix: jax.Array, op: str) -> jax.Array:
    """Shard-local reference combine: left-fold over γ_max, bit-identical to
    :func:`repro.core.density_map.combine_densities_batch_np` on the slab
    (both reduce the tiny γ axis as a sequential left fold in f32)."""
    gamma = row_matrix.shape[1]
    sel = dens_local[jnp.maximum(row_matrix, 0)]  # [Q, γ_max, λ_local]
    valid = (row_matrix >= 0)[..., None]
    ident = jnp.float32(1.0 if op == "and" else 0.0)
    acc = jnp.full((sel.shape[0], sel.shape[2]), ident)  # [Q, λ_local]
    for j in range(gamma):
        term = jnp.where(valid[:, j], sel[:, j], ident)
        acc = acc * term if op == "and" else acc + term
    if op == "or":
        acc = jnp.minimum(acc, jnp.float32(1.0))
    return acc


def density_combine_batch_sharded(
    densities: jax.Array,  # [rows, lam] f32, λ sharded over `axis`
    row_matrix: jax.Array,  # [Q, gamma_max] int32, padded with -1
    mesh,
    op: str = "and",
    axis: str = "data",
    use_kernel: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Wave combine on a λ-sharded density tensor: ``[Q, λ]`` out, sharded.

    Parameters
    ----------
    densities : jax.Array
        ``[rows, λ]`` density tensor placed with ``P(None, axis)`` (see
        :func:`repro.core.sharded.shard_density_maps`).
    row_matrix : jax.Array
        ``[Q, γ_max]`` predicate row ids, right-padded with ``-1``
        (:func:`repro.core.density_map.pack_row_matrix`).
    mesh : jax.sharding.Mesh
        Mesh whose ``axis`` dimension shards λ.
    op : str
        ``"and"`` (product) or ``"or"`` (clipped sum), paper §3.2.
    use_kernel : bool
        Route each shard's local combine through the
        :func:`density_combine_batch` Pallas kernel (TPU; pair with
        ``interpret=True`` elsewhere).  Default is the jnp left fold, which is
        bit-identical to the host combine on every backend.

    Returns
    -------
    jax.Array
        ``[Q, λ]`` combined matrix, sharded ``P(None, axis)`` — each query row
        bit-identical to its single-query §3.2 combine.
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    def body(dens_local: jax.Array, rm: jax.Array) -> jax.Array:
        if use_kernel:
            return density_combine_batch(dens_local, rm, op, interpret=interpret)
        return _combine_local(dens_local, rm, op)

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(None, axis), P()),
        out_specs=P(None, axis),
        check_vma=False,
    )
    return fn(densities, row_matrix.astype(jnp.int32))
