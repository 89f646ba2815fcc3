"""Multi-threshold masked statistics Pallas kernel — the THRESHOLD back end
(paper §4.1).

THRESHOLD's invariant is a running threshold θ: a block joins the output iff its
combined density clears θ.  The TPU-native realization bisects on θ directly:
for a batch of T candidate thresholds this kernel returns, in one pass over the
λ blocks,

    counts[t]  = #{b : density[b] >= θ_t}        (blocks that would be selected)
    recsum[t]  = Σ_{b : density[b] >= θ_t} density[b]   (expected records / R)

The wrapper refines θ over a few rounds until the smallest θ with
``recsum·records_per_block ≥ k`` is pinned — O(rounds·λ) streamed work with no
sort and no materialized candidate list, versus O(λ log λ) for the sort-based
form.  This is the kernel the §Perf hillclimb of the paper-technique cell tunes.

:func:`theta_stats_batch` is the wave form: ``[Q, λ]`` combined rows × per-query
``[Q, T]`` candidate thresholds produce both ``[Q, T]`` statistics in one launch
— the shard-local reduction step of the batched distributed θ-bisection
(:func:`repro.core.sharded.sharded_threshold_bisect_batch`), where one psum of
``Q·2·T`` floats then merges all shards for the whole wave.

Grid: ``(λ_tiles,)`` scalar / ``(Q, λ_tiles)`` batched, outputs accumulated
across λ steps (the ``[T]`` / ``[1, T, 1]`` output blocks are revisited every step;
the query axis is outermost and parallel-safe, mirroring
:func:`repro.kernels.density_combine.density_combine_batch`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


TILE = 2048


def _kernel(x_ref, thetas_ref, counts_ref, recsum_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        recsum_ref[...] = jnp.zeros_like(recsum_ref)

    x = x_ref[...]  # [TILE]
    th = thetas_ref[...]  # [T]
    m = x[None, :] >= th[:, None]  # [T, TILE]
    counts_ref[...] += jnp.sum(m, axis=1).astype(jnp.float32)
    recsum_ref[...] += jnp.sum(jnp.where(m, x[None, :], 0.0), axis=1)


def theta_stats(
    combined: jax.Array,  # [lam] f32
    thetas: jax.Array,  # [T] f32 candidate thresholds (T multiple of 8)
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    (lam,) = combined.shape
    (T,) = thetas.shape
    pad = (-lam) % TILE
    if pad:
        combined = jnp.pad(combined, (0, pad), constant_values=-1.0)  # never >= θ>0
    counts, recsum = pl.pallas_call(
        _kernel,
        grid=(combined.shape[0] // TILE,),
        in_specs=[
            pl.BlockSpec((TILE,), lambda i: (i,)),
            pl.BlockSpec((T,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((T,), lambda i: (0,)),
            pl.BlockSpec((T,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T,), jnp.float32),
            jax.ShapeDtypeStruct((T,), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(combined, thetas)
    return counts, recsum


def _batch_kernel(x_ref, thetas_ref, counts_ref, recsum_ref):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        recsum_ref[...] = jnp.zeros_like(recsum_ref)

    x = x_ref[0]  # [1, TILE] this query's λ-tile
    th = thetas_ref[0]  # [T, 1] this query's candidate thresholds
    m = x >= th  # [T, TILE]
    counts_ref[...] += jnp.sum(m, axis=1, keepdims=True).astype(jnp.float32)[None]
    recsum_ref[...] += jnp.sum(jnp.where(m, x, 0.0), axis=1, keepdims=True)[None]


def theta_stats_batch(
    combined: jax.Array,  # [Q, lam] f32 one combined-density row per query
    thetas: jax.Array,  # [Q, T] f32 per-query candidate thresholds
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Batched masked θ-statistics: ``[Q, T]`` counts and density sums.

    Parameters
    ----------
    combined : jax.Array
        ``[Q, λ]`` float32 ⊕-combined density rows, one per wave query.
    thetas : jax.Array
        ``[Q, T]`` float32 candidate thresholds; each query bisects its own
        θ bracket, so rows are independent.
    interpret : bool
        Run the Pallas kernel in interpret mode (CPU tests).

    Returns
    -------
    (counts, recsum) : tuple[jax.Array, jax.Array]
        ``[Q, T]`` each: ``counts[q, t] = #{b : combined[q, b] >= thetas[q, t]}``
        and ``recsum[q, t] = Σ_{b : combined[q, b] >= thetas[q, t]} combined[q, b]``
        — row q bit-identical to ``theta_stats(combined[q], thetas[q])``.

    Notes
    -----
    Rows travel as ``[Q, 1, λ]`` and thresholds as ``[Q, T, 1]`` so every
    block's last two dims equal the array's (the TPU lowering rejects a
    ``(1, TILE)`` block over a 2-D ``[Q, λ]`` array), and the ``[T, TILE]``
    comparison needs no in-kernel transpose.
    """
    nq, lam = combined.shape
    _, T = thetas.shape
    pad = (-lam) % TILE
    if pad:
        combined = jnp.pad(
            combined, ((0, 0), (0, pad)), constant_values=-1.0
        )  # never >= θ>0
    lam_p = lam + pad
    counts, recsum = pl.pallas_call(
        _batch_kernel,
        grid=(nq, lam_p // TILE),
        in_specs=[
            pl.BlockSpec((1, 1, TILE), lambda q, i: (q, 0, i)),
            pl.BlockSpec((1, T, 1), lambda q, i: (q, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, T, 1), lambda q, i: (q, 0, 0)),
            pl.BlockSpec((1, T, 1), lambda q, i: (q, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, T, 1), jnp.float32),
            jax.ShapeDtypeStruct((nq, T, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
    )(combined.reshape(nq, 1, lam_p), thetas.reshape(nq, T, 1))
    return counts[:, :, 0], recsum[:, :, 0]
