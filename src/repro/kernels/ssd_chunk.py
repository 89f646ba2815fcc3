"""Mamba2 SSD (state-space duality) chunked-scan Pallas kernel.

The SSD recurrence  h_t = a_t·h_{t-1} + B_t ⊗ u_t,  y_t = C_t·h_t  is evaluated
chunk-wise (Mamba2 paper, Listing 1) so that all heavy work is MXU matmuls:

  intra-chunk:  Y_intra = (C Bᵀ ⊙ L) @ U        L[t,s] = exp(ca_t − ca_s)·1[s≤t]
  state carry:  H_next  = exp(ca_Q)·H_prev + (exp(ca_Q − ca)·B)ᵀ @ U
  inter-chunk:  Y_inter = exp(ca)·(C @ H_prev)

with ca = inclusive cumsum of the per-step log-decays inside the chunk.

Grid: ``(B, H, num_chunks)`` — chunks innermost (sequential); the running state
``H ∈ [ds, dh]`` lives in VMEM scratch across chunk steps.  Chunk length Q = 128
aligns every matmul with the MXU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


CHUNK = 128


def _kernel(u_ref, ld_ref, b_ref, c_ref, y_ref, h_ref):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    u = u_ref[0, 0].astype(jnp.float32)  # [Q, dh]
    ld = ld_ref[0, 0].astype(jnp.float32)  # [Q]
    bm = b_ref[0, 0].astype(jnp.float32)  # [Q, ds]
    cm = c_ref[0, 0].astype(jnp.float32)  # [Q, ds]

    # inclusive cumsum of log-decays via triangular matmul (MXU path)
    r = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
    tri = (c <= r).astype(jnp.float32)
    ca = jnp.dot(tri, ld.reshape(CHUNK, 1), preferred_element_type=jnp.float32)
    ca = ca.reshape(CHUNK)  # ca[t] = sum_{s<=t} ld[s]

    # decay matrix L[t, s] = exp(ca_t - ca_s) for s <= t (a_s excluded? note:
    # recurrence applies a_t before adding B_t u_t, so contribution of step s to
    # step t is prod_{r=s+1..t} a_r = exp(ca_t - ca_s))
    L = jnp.exp(ca[:, None] - ca[None, :]) * tri
    scores = jnp.dot(cm, bm.T, preferred_element_type=jnp.float32) * L  # [Q, Q]
    y = jnp.dot(scores, u, preferred_element_type=jnp.float32)  # intra-chunk

    # inter-chunk: contribution of carried state
    h = h_ref[...]  # [ds, dh]
    y += jnp.exp(ca)[:, None] * jnp.dot(cm, h, preferred_element_type=jnp.float32)

    # state update for next chunk
    wb = jnp.exp(ca[CHUNK - 1] - ca)[:, None] * bm  # [Q, ds]
    h_ref[...] = jnp.exp(ca[CHUNK - 1]) * h + jnp.dot(
        wb.T, u, preferred_element_type=jnp.float32
    )
    y_ref[0, 0] = y.astype(y_ref.dtype)


def ssd_scan(
    u: jax.Array,  # [B, H, S, dh] dt-scaled inputs (dt*x)
    ldecay: jax.Array,  # [B, H, S] log decays (dt*A, A<0)
    bmat: jax.Array,  # [B, H, S, ds]
    cmat: jax.Array,  # [B, H, S, ds]
    interpret: bool = False,
) -> jax.Array:
    """Returns y [B, H, S, dh]. S must be a multiple of CHUNK (pad upstream)."""
    b, h, s, dh = u.shape
    ds = bmat.shape[-1]
    assert s % CHUNK == 0, "pad sequence to CHUNK"
    grid = (b, h, s // CHUNK)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, CHUNK, dh), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, CHUNK), lambda bi, hi, ci: (bi, hi, ci)),
            pl.BlockSpec((1, 1, CHUNK, ds), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, CHUNK, ds), lambda bi, hi, ci: (bi, hi, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, CHUNK, dh), lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, dh), u.dtype),
        scratch_shapes=[pltpu.VMEM((ds, dh), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(u, ldecay, bmat, cmat)
