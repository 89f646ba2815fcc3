"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _arr(shape, dtype=np.float32, scale=1.0):
    return jnp.asarray((RNG.normal(0, 1, shape) * scale).astype(dtype))


@pytest.mark.parametrize("rows,lam", [(4, 100), (16, 513), (64, 2048), (16, 1537)])
@pytest.mark.parametrize("gamma", [1, 2, 5])
@pytest.mark.parametrize("op", ["and", "or"])
def test_density_combine_sweep(rows, lam, gamma, op):
    dens = jnp.asarray(RNG.random((rows, lam)).astype(np.float32))
    rids = jnp.asarray(RNG.integers(0, rows, gamma), jnp.int32)
    out = ops.density_combine(dens, rids, op=op)
    expect = ref.density_combine_ref(dens, rids, op=op)
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("rows,lam", [(8, 100), (16, 513), (32, 2048)])
@pytest.mark.parametrize("nq,gmax", [(1, 1), (4, 3), (9, 5)])
@pytest.mark.parametrize("op", ["and", "or"])
def test_density_combine_batch_sweep(rows, lam, nq, gmax, op):
    dens = jnp.asarray(RNG.random((rows, lam)).astype(np.float32))
    rm = RNG.integers(0, rows, (nq, gmax)).astype(np.int32)
    # ragged batch: random right-padding per query (at least one live row)
    for q in range(nq):
        g = int(RNG.integers(1, gmax + 1))
        rm[q, g:] = -1
    rm = jnp.asarray(rm)
    out = ops.density_combine_batch(dens, rm, op=op)
    expect = ref.density_combine_batch_ref(dens, rm, op=op)
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-6)
    # each row must equal the single-query kernel on its unpadded rows
    for q in range(nq):
        rids = rm[q][rm[q] >= 0]
        single = ops.density_combine(dens, rids, op=op)
        np.testing.assert_allclose(out[q], single, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("n", [1, 100, 1024, 5000])
def test_prefix_sum_sweep(n):
    x = jnp.asarray(RNG.random(n).astype(np.float32))
    np.testing.assert_allclose(
        ops.prefix_sum(x), ref.prefix_sum_ref(x), rtol=1e-4, atol=1e-3
    )


@pytest.mark.parametrize("lam,T", [(100, 8), (4096, 16), (10_000, 32)])
def test_theta_stats_sweep(lam, T):
    comb = jnp.asarray((RNG.random(lam) * (RNG.random(lam) < 0.4)).astype(np.float32))
    ths = jnp.asarray(np.linspace(0.01, 0.95, T).astype(np.float32))
    c1, r1 = ops.theta_stats(comb, ths)
    c2, r2 = ref.theta_stats_ref(comb, ths)
    np.testing.assert_allclose(c1, c2)
    np.testing.assert_allclose(r1, r2, rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize(
    "nq,lam,T", [(1, 100, 8), (4, 4096, 16), (9, 1000, 16), (3, 2049, 8)]
)
def test_theta_stats_batch_sweep(nq, lam, T):
    comb = jnp.asarray(
        (RNG.random((nq, lam)) * (RNG.random((nq, lam)) < 0.4)).astype(np.float32)
    )
    ths = jnp.asarray(
        np.stack([np.linspace(0.01 * (q + 1), 0.95, T) for q in range(nq)]).astype(
            np.float32
        )
    )
    cb, rb = ops.theta_stats_batch(comb, ths)
    ce, re_ = ref.theta_stats_batch_ref(comb, ths)
    np.testing.assert_allclose(cb, ce)
    np.testing.assert_allclose(rb, re_, rtol=1e-5, atol=1e-3)
    # each row must equal the single-query kernel bit-for-bit in counts
    for q in range(nq):
        c1, r1 = ops.theta_stats(comb[q], ths[q])
        np.testing.assert_array_equal(np.asarray(cb)[q], np.asarray(c1))
        np.testing.assert_allclose(np.asarray(rb)[q], np.asarray(r1), rtol=1e-6)


@pytest.mark.parametrize(
    "lam,r,d", [(16, 8, 2), (100, 32, 1), (257, 16, 3), (12, 8, 256), (9, 2, 128)]
)
def test_block_gather_sweep(lam, r, d):
    """One-launch union gather vs the pure indexing oracle, incl. the store's
    lane-dense ``[λ, r, R]`` slabs (R = d minor), 2-D slabs, repeated ids,
    and the empty union."""
    slab = jnp.asarray(RNG.random((lam, r, d)).astype(np.float32))
    ids = jnp.asarray(RNG.integers(0, lam, 7).astype(np.int32))
    np.testing.assert_array_equal(
        ops.block_gather(slab, ids), ref.block_gather_ref(slab, ids)
    )
    flat = jnp.asarray(RNG.integers(0, 5, (lam, r)).astype(np.int32))
    np.testing.assert_array_equal(
        ops.block_gather(flat, ids), ref.block_gather_ref(flat, ids)
    )
    empty = jnp.asarray(np.zeros((0,), np.int32))
    assert ops.block_gather(slab, empty).shape == (0, r, d)


@pytest.mark.parametrize("nq,lam", [(1, 64), (5, 129), (8, 1000)])
@pytest.mark.parametrize("op", ["and", "or"])
def test_plan_wave_matches_ref(nq, lam, op):
    """Fused combine → θ-stats → sort → cut vs the per-query oracles: the
    THRESHOLD masks, cursors, and TWO-PRONG windows must match exactly, the
    θ-stats must certify the running-threshold invariant on device."""
    from repro.kernels.plan_wave import plan_wave

    rows = 8
    dens = jnp.asarray(
        (RNG.random((rows, lam)) * (RNG.random((rows, lam)) < 0.4)).astype(np.float32)
    )
    rm = RNG.integers(0, rows, (nq, 3)).astype(np.int32)
    rm[0, 1:] = -1  # ragged wave
    excl = jnp.asarray(RNG.random((nq, lam)) < 0.15)
    needs = jnp.asarray(RNG.integers(1, 5 * lam, nq).astype(np.float32))
    res = plan_wave(dens, jnp.asarray(rm), excl, needs, 10, op=op)
    rth, rn, rtheta, rtc, rexp, rs, re_ = ref.plan_wave_ref(
        dens, jnp.asarray(rm), excl, needs, 10, op=op
    )
    # discrete outputs are exact; float diagnostics are allclose targets (the
    # pipeline combines with the host's sequential fold, the oracle with
    # jnp.prod — same mask/cursor decisions, last-ulp value differences)
    np.testing.assert_array_equal(np.asarray(res.th_mask), np.asarray(rth))
    np.testing.assert_array_equal(np.asarray(res.n_sel), np.asarray(rn))
    np.testing.assert_allclose(
        np.asarray(res.theta), np.asarray(rtheta), rtol=1e-5, atol=1e-7
    )
    np.testing.assert_array_equal(np.asarray(res.tp_start), np.asarray(rs))
    np.testing.assert_array_equal(np.asarray(res.tp_end), np.asarray(re_))
    np.testing.assert_allclose(
        np.asarray(res.expected_records), np.asarray(rexp), rtol=1e-5, atol=1e-3
    )
    # §4.1 running-threshold invariant, certified by the θ-stats chain
    assert np.all(np.asarray(res.theta_count) >= np.asarray(res.n_sel))
    # exclusion masking really happened: no selected block is excluded
    assert not np.any(np.asarray(res.th_mask) & np.asarray(excl))
    # the Pallas-kernel route (combine + θ-stats kernels, interpret on CPU)
    # agrees with the jnp-fold route on the discrete outputs
    resk = ops.plan_wave(dens, jnp.asarray(rm), excl, needs, 10, op=op)
    np.testing.assert_array_equal(np.asarray(resk.th_mask), np.asarray(rth))
    np.testing.assert_array_equal(np.asarray(resk.n_sel), np.asarray(rn))


def test_threshold_bisect_matches_sort_selection():
    from repro.core.threshold import threshold_select

    comb = jnp.asarray((RNG.random(5000) * (RNG.random(5000) < 0.3)).astype(np.float32))
    for k in (10.0, 200.0, 3000.0):
        theta = ops.threshold_bisect(comb, k, 10)
        n_bisect = int(jnp.sum(comb >= theta))
        n_sort = int(threshold_select(comb, k, 10).num_selected)
        assert abs(n_bisect - n_sort) <= max(2, 0.01 * n_sort)


@pytest.mark.slow
@pytest.mark.parametrize(
    "b,hq,hkv,s,t,causal,win",
    [
        (1, 2, 1, 128, 128, True, None),
        (2, 4, 4, 100, 100, True, None),   # padding
        (1, 4, 2, 128, 256, True, None),   # decode-style (q shorter, right-aligned)
        (1, 2, 1, 200, 200, True, 64),     # sliding window
        (1, 2, 2, 64, 192, False, None),   # cross-attention
    ],
)
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, hq, hkv, s, t, causal, win, dtype):
    q, k, v = _arr((b, hq, s, 128), dtype), _arr((b, hkv, t, 128), dtype), _arr((b, hkv, t, 128), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=win)
    expect = ref.attention_ref(q, k, v, causal=causal, window=win)
    tol = 2e-3 if dtype == np.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.slow
@pytest.mark.parametrize("b,h,s,dh,ds", [(1, 1, 128, 32, 16), (2, 3, 256, 64, 32)])
def test_ssd_scan_sweep(b, h, s, dh, ds):
    u = _arr((b, h, s, dh), scale=0.1)
    ld = -jnp.abs(_arr((b, h, s), scale=0.1))
    bm, cm = _arr((b, h, s, ds), scale=0.3), _arr((b, h, s, ds), scale=0.3)
    y = ops.ssd_scan(u, ld, bm, cm)
    yref, _ = ref.ssd_ref(u, ld, bm, cm)
    np.testing.assert_allclose(y, yref, atol=2e-3, rtol=1e-2)


def test_ssd_chunked_matches_ref_and_returns_state():
    from repro.models.layers import ssd_chunked

    b, h, s, dh, ds = 1, 2, 256, 32, 16
    u = _arr((b, h, s, dh), scale=0.1)
    ld = -jnp.abs(_arr((b, h, s), scale=0.1))
    bm, cm = _arr((b, h, s, ds), scale=0.3), _arr((b, h, s, ds), scale=0.3)
    y, hfin = ssd_chunked(u, ld, bm, cm, 128, return_state=True)
    yref, href = ref.ssd_ref(u, ld, bm, cm)
    np.testing.assert_allclose(y, yref, atol=2e-3, rtol=1e-2)
    np.testing.assert_allclose(hfin, href, atol=2e-3, rtol=1e-2)
