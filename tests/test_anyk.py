"""Any-k algorithms: faithful ports vs TPU-vectorized forms + optimality
properties (paper §4, Theorems 1-3)."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.cost_model import make_cost_model
from repro.core.density_map import combine_densities_np
from repro.core.forward_optimal import forward_optimal_faithful, forward_optimal_scan
from repro.core.threshold import threshold_faithful, threshold_select
from repro.core.two_prong import (
    rank_left, two_prong_faithful, two_prong_select, two_prong_select_batch,
)

RPB = 20


def _densities(seed, lam=64, rows=4):
    rng = np.random.default_rng(seed)
    d = rng.random((rows, lam)).astype(np.float32)
    d[rng.random((rows, lam)) < 0.4] = 0.0
    return d


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([1, 7, 50, 400, 10_000]))
def test_threshold_vectorized_equals_faithful(seed, k):
    dens = _densities(seed)
    rows = np.asarray([0, 2], np.int32)
    comb = combine_densities_np(dens, rows)
    faithful = threshold_faithful(dens, rows, k, RPB)
    r = threshold_select(jnp.asarray(comb), float(k), RPB)
    vect = np.asarray(r.block_ids)[: int(r.num_selected)].tolist()
    assert set(faithful) == set(vect)
    # and both orderings are density-descending
    assert all(comb[a] >= comb[b] - 1e-6 for a, b in zip(vect, vect[1:]))


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([1, 13, 100, 900]))
def test_threshold_density_optimality(seed, k):
    """Theorem 1: selected set = densest blocks with >= k expected records."""
    dens = _densities(seed)
    comb = combine_densities_np(dens, np.asarray([1, 3]))
    r = threshold_select(jnp.asarray(comb), float(k), RPB)
    n = int(r.num_selected)
    sel = np.asarray(r.block_ids)[:n]
    unsel = np.setdiff1d(np.arange(comb.shape[0]), sel)
    if n:
        # every selected block at least as dense as every unselected one
        assert comb[sel].min() >= (comb[unsel].max() if unsel.size else 0.0) - 1e-6
        # minimality: dropping the least dense selected block goes below k
        if float(r.expected_records) >= k:
            assert (comb[sel].sum() - comb[sel].min()) * RPB < k


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([1, 13, 100, 900]))
def test_two_prong_vectorized_equals_faithful(seed, k):
    dens = _densities(seed)
    comb = combine_densities_np(dens, np.asarray([0, 1]))
    fs, fe = two_prong_faithful(comb, k, RPB)
    r = two_prong_select(jnp.asarray(comb), float(k), RPB)
    vs, ve = int(r.start), int(r.end)
    assert (vs, ve) == (fs, fe)


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10_000))
def test_two_prong_locality_optimality(seed):
    """Theorem 2: no shorter window holds >= k expected records (brute force)."""
    dens = _densities(seed, lam=32)
    comb = combine_densities_np(dens, np.asarray([0]))
    k = max(int(comb.sum() * RPB * 0.3), 1)
    r = two_prong_select(jnp.asarray(comb), float(k), RPB)
    vs, ve = int(r.start), int(r.end)
    got = comb[vs:ve].sum() * RPB
    if got >= k:  # feasible instance
        best = ve - vs
        c = np.concatenate([[0.0], np.cumsum(comb)]) * RPB
        for s in range(32):
            for e in range(s + 1, 33):
                if c[e] - c[s] >= k:
                    assert e - s >= best
                    break


# Window-end cases for the sort-merge rank.  Densities are multiples of
# 1/EXACT_RPB, so every prefix sum is an exact integer and a target can equal
# a prefix sum exactly.
EXACT_RPB = 16


def _clustered_row(seed, lam=96):
    """Runs of equal density between zero plateaus."""
    rng = np.random.default_rng(seed)
    row = np.zeros(lam, np.float32)
    for s in rng.integers(0, lam, 6):
        row[s : s + rng.integers(2, 12)] = rng.integers(1, EXACT_RPB + 1) / EXACT_RPB
    return row


def _mass(row, lo, hi):
    return float(np.sum(row[lo:hi], dtype=np.float64) * EXACT_RPB)


def _window_case(name):
    """``(rows [Q, λ] f32, needs [Q] f32)`` of one named case."""
    if name == "clustered_plateaus":
        rows, needs = [_clustered_row(1)], [40.0]
    elif name == "tied_densities":
        rows = [np.tile(np.float32([2, 2, 0, 5, 5, 5]) / EXACT_RPB, 16)]
        needs = [21.0]
    elif name == "k_on_prefix_sum":
        row = _clustered_row(2)
        rows, needs = [row, row], [_mass(row, 0, 60), _mass(row, 17, 50)]
    elif name == "infeasible":
        row = _clustered_row(3)
        rows, needs = [row], [_mass(row, 0, None) + 1.0]
    elif name == "all_zero":
        rows, needs = [np.zeros(96, np.float32)], [5.0]
    elif name == "lambda_one":
        row = np.float32([7 / EXACT_RPB])
        rows, needs = [row, row, row], [3.0, 7.0, 8.0]
    elif name == "exclusion_zeroed":
        rng = np.random.default_rng(4)
        dense = (rng.integers(1, EXACT_RPB + 1, 96) / EXACT_RPB).astype(np.float32)
        rows = [np.where(rng.random(96) < p, 0.0, dense) for p in (0.3, 0.9)]
        needs = [100.0, 30.0]
    elif name == "batched":
        rows = [_clustered_row(s) for s in range(5)]
        rows += [np.zeros(96, np.float32), np.tile(rows[0][:48], 2)]
        needs = [40.0, 1.0, _mass(rows[2], 3, 70), 1e6, 17.0, 5.0, 40.0]
    else:  # random_float: f32 rounding in the prefix sums
        rng = np.random.default_rng(int(name.rsplit("_", 1)[1]))
        d = rng.random((4, 96)).astype(np.float32)
        d[rng.random((4, 96)) < 0.5] = 0.0
        rows, needs = list(d), list(rng.choice([1.0, 13.0, 100.0, 400.0], 4))
    return np.stack(rows).astype(np.float32), np.asarray(needs, np.float32)


def _ends_by_scan(row, k):
    """Window ends as ``two_prong_select`` forms them, searched by the default
    scan ``jnp.searchsorted``: the oracle the sort-merge rank replaces."""
    m = jnp.asarray(row) * EXACT_RPB
    c = jnp.concatenate([jnp.zeros((1,), m.dtype), jnp.cumsum(m)])
    targets = c[:-1] + jnp.float32(k)
    return c, targets, np.asarray(jnp.searchsorted(c, targets, side="left"))


@pytest.mark.parametrize("case", [
    "clustered_plateaus", "tied_densities", "k_on_prefix_sum", "infeasible",
    "all_zero", "lambda_one", "exclusion_zeroed", "batched",
    "random_float_5", "random_float_6",
])
def test_two_prong_window_ends_equal_searchsorted(case):
    rows, needs = _window_case(case)
    lam = rows.shape[1]
    batch = two_prong_select_batch(jnp.asarray(rows), jnp.asarray(needs), EXACT_RPB)
    for q, (row, k) in enumerate(zip(rows, needs)):
        c, targets, ends = _ends_by_scan(row, k)
        np.testing.assert_array_equal(np.asarray(rank_left(c, targets)), ends)
        lengths = np.where(ends <= lam, ends - np.arange(lam), np.iinfo(np.int32).max)
        want = (int(np.argmin(lengths)), int(ends[np.argmin(lengths)]))
        if (ends > lam).all():
            want = (0, lam)
        alone = two_prong_select(jnp.asarray(row), float(k), EXACT_RPB)
        assert (int(alone.start), int(alone.end)) == want
        assert (int(batch.start[q]), int(batch.end[q])) == want
        assert float(batch.expected_records[q]) == float(alone.expected_records)
        if not case.startswith("random_float"):  # exact sums: the walk agrees
            assert two_prong_faithful(row, float(k), EXACT_RPB) == want


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 1000))
def test_forward_optimal_brute_force(seed):
    """Theorem 3 on tiny instances: DP cost == exhaustive-search cost."""
    import itertools

    rng = np.random.default_rng(seed)
    lam, k = 8, 6
    cm = dataclasses.replace(make_cost_model("hdd"), max_dist=3)
    comb = np.where(rng.random(lam) < 0.6, rng.random(lam) * 0.5, 0.0).astype(np.float32)
    s_blk = np.clip(np.rint(comb * 10), 0, k)
    sel, cost = forward_optimal_faithful(comb, k, 10, cm)
    if not np.isfinite(cost):
        return
    best = np.inf
    for r in range(1, lam + 1):
        for subset in itertools.combinations(range(lam), r):
            if s_blk[list(subset)].sum() >= k:
                best = min(best, cm.io_time(list(subset)))
    assert cost == pytest.approx(best, rel=1e-6)
    # scan DP agrees with the faithful DP
    r2 = forward_optimal_scan(jnp.asarray(comb), k, 10, cm)
    assert float(r2.opt_cost) == pytest.approx(cost, rel=1e-4)


def test_engine_returns_only_valid_records():
    from repro.core.engine import NeedleTailEngine
    from repro.data.block_store import build_block_store
    from repro.data.synthetic import make_clustered_table

    t = make_clustered_table(num_records=20_000, num_dims=4, density=0.15, seed=2)
    store = build_block_store(t, records_per_block=100)
    eng = NeedleTailEngine(store)
    preds = [(0, 1), (2, 1)]
    for algo in ("threshold", "two_prong", "auto"):
        r = eng.any_k(preds, k=300, algo=algo)
        dims = np.asarray(store.dims)
        for b, row in zip(r.record_block, r.record_row):
            assert dims[b, row, 0] == 1 and dims[b, row, 2] == 1
        want = min(300, int(t.valid_mask(preds).sum()))
        assert r.num_records >= want  # engine refills until satisfied


def test_engine_refill_on_underdelivery():
    """Density-estimate overconfidence must trigger re-execution (§4.1)."""
    from repro.core.engine import NeedleTailEngine
    from repro.data.block_store import Table, build_block_store

    # adversarial: A0=1 and A1=1 never co-occur in dense blocks, only in a few
    rng = np.random.default_rng(0)
    n = 4000
    a0 = np.zeros(n, np.int32)
    a1 = np.zeros(n, np.int32)
    a0[:2000] = 1  # first half
    a1[1000:3000] = 1  # middle: overlap region 1000-2000 only
    dims = np.stack([a0, a1], axis=1)
    t = Table(dims=dims, measures=rng.normal(size=(n, 1)).astype(np.float32),
              cards=np.asarray([2, 2]))
    store = build_block_store(t, records_per_block=100)
    eng = NeedleTailEngine(store)
    r = eng.any_k([(0, 1), (1, 1)], k=900, algo="threshold")
    assert r.num_records >= 900
