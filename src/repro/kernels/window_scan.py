"""Blocked prefix-sum (cumulative expected-records) Pallas kernel — the TWO-PRONG
front end (paper §4.2).

TWO-PRONG needs the running cumulative sum ``c[i] = Σ_{b<i} density[b]·R`` over all
λ blocks; the minimal window search then operates on ``c``.  This kernel computes
the exact inclusive prefix sum in one HBM pass:

* intra-tile prefix sums run on the MXU as a lower-triangular matmul
  (``tri(T,T) @ x(T,1)`` — the classic systolic scan trick; no serial VPU loop),
* the inter-tile carry lives in SMEM scratch and flows across the sequential TPU
  grid.

The λ-tile is (8, 128)-shaped f32 so the triangular matmul is a single
1024×1024-free MXU op per tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


TILE = 1024  # per-grid-step λ tile


def _kernel(x_ref, out_ref, carry_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        carry_ref[0] = 0.0

    x = x_ref[...].astype(jnp.float32).reshape(TILE, 1)
    # inclusive prefix sum via lower-triangular ones matmul (MXU path)
    r = jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 1)
    tri = (c <= r).astype(jnp.float32)
    csum = jnp.dot(tri, x, preferred_element_type=jnp.float32).reshape(TILE)
    out_ref[...] = csum + carry_ref[0]
    carry_ref[0] += csum[TILE - 1]


def prefix_sum(x: jax.Array, interpret: bool = False) -> jax.Array:
    """Exact inclusive prefix sum of a 1-D f32 vector (any length)."""
    (lam,) = x.shape
    pad = (-lam) % TILE
    if pad:
        x = jnp.pad(x, (0, pad))
    out = pl.pallas_call(
        _kernel,
        grid=(x.shape[0] // TILE,),
        in_specs=[pl.BlockSpec((TILE,), lambda i: (i,))],
        out_specs=pl.BlockSpec((TILE,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        scratch_shapes=[pltpu.SMEM((1,), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(x)
    return out[:lam]
