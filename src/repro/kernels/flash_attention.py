"""Tiled online-softmax attention (flash-attention) Pallas kernel.

TPU-native tiling: q tiles of (TILE_Q, D) stay VMEM-resident while kv tiles of
(TILE_KV, D) stream HBM→VMEM; softmax state (m, l) and the output accumulator
live in VMEM scratch across the kv grid axis.  Supports causal masking,
sliding-window (SWA) masking, and GQA (q-head → kv-head mapping happens in the
kv ``index_map``, so kv tiles are fetched once per q-head group position).

MXU alignment: TILE_Q = TILE_KV = 128, D padded to a multiple of 128 by the
caller (models use head_dim ∈ {64, 128}; 64 is padded — documented waste, or use
the xla path).  Fully-masked kv tiles are skipped with ``pl.when`` (halves the
causal work).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


TILE_Q = 128
TILE_KV = 128
NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale: float, causal: bool, window: int | None, t_total: int, s_total: int,
):
    j = pl.program_id(3)
    nj = pl.num_programs(3)
    qi = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute positions (decoder alignment: query block right-aligned to kv end)
    q_pos = qi * TILE_Q + jax.lax.broadcasted_iota(jnp.int32, (TILE_Q, TILE_KV), 0)
    q_pos = q_pos + (t_total - s_total)
    k_pos = j * TILE_KV + jax.lax.broadcasted_iota(jnp.int32, (TILE_Q, TILE_KV), 1)

    def tile_visible() -> jax.Array:
        vis = jnp.bool_(True)
        if causal:  # some q in tile sees some k in tile
            vis &= (qi * TILE_Q + TILE_Q - 1 + (t_total - s_total)) >= j * TILE_KV
        if window is not None:  # newest k in tile within window of newest q
            vis &= (qi * TILE_Q + (t_total - s_total)) - (j * TILE_KV + TILE_KV - 1) < window
        return vis

    @pl.when(tile_visible())
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [TQ, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [TKV, D]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        mask = k_pos < t_total  # kv padding is never attended
        if causal:
            mask = mask & (q_pos >= k_pos)
        if window is not None:
            mask = mask & ((q_pos - k_pos) < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(j == nj - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # [B, Hq, S, D]
    k: jax.Array,  # [B, Hkv, T, D]
    v: jax.Array,  # [B, Hkv, T, D]
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    b, hq, s, d = q.shape
    _, hkv, t, _ = k.shape
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / (d**0.5)
    pad_q = (-s) % TILE_Q
    pad_kv = (-t) % TILE_KV
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
    sp, tp = s + pad_q, t + pad_kv
    grid = (b, hq, sp // TILE_Q, tp // TILE_KV)

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, causal=causal, window=window,
            t_total=t, s_total=s,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, TILE_Q, d), lambda bi, h, i, j: (bi, h, i, 0)),
            pl.BlockSpec((1, 1, TILE_KV, d), lambda bi, h, i, j: (bi, h // g, j, 0)),
            pl.BlockSpec((1, 1, TILE_KV, d), lambda bi, h, i, j: (bi, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, TILE_Q, d), lambda bi, h, i, j: (bi, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((TILE_Q,), jnp.float32),
            pltpu.VMEM((TILE_Q,), jnp.float32),
            pltpu.VMEM((TILE_Q, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
    )(q, k, v)
    return out[:, :, :s, :]
