"""TWO-PRONG — locality-optimal any-k block selection (paper §4.2, Algorithm 2).

* :func:`two_prong_faithful` — 1:1 host port of Algorithm 2 (two-pointer walk).
* :func:`two_prong_select` — TPU-native outcome-equivalent form: prefix sums +
  the minimal window end of every start, then an argmin over starts.  For every
  start block i this computes the same "smallest sequence beginning at i with
  ≥ k expected records" that the two-pointer walk considers (Theorem 2 proof
  structure), so the global minimum window is identical; ties resolve to the
  smallest start id in both.  The ends are a rank by sort-merge
  (:func:`rank_left`): two sorts and a prefix count, O(λ log λ) work with no
  gather, scatter or loop — the sequential walk is O(λ) work but O(λ) depth,
  and a per-start binary search is a loop of ⌈log2 λ⌉ gathers, both the wrong
  trade on a vector machine.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def two_prong_faithful(
    combined: np.ndarray, k: int, records_per_block: int
) -> tuple[int, int]:
    """Algorithm 2, line for line. Returns [start, end) of the minimal window.

    Follows the paper exactly, including its guard behaviour: if fewer than k
    valid records exist in total, the window degenerates to the initial state.
    """
    m = np.asarray(combined, dtype=np.float64) * records_per_block
    lam = m.shape[0]
    tau = 0.0
    start = end = 0
    min_start, min_end = 0, lam + 1  # sentinel: "no window found yet"
    while end < lam:
        while tau < k and end < lam:
            tau += m[end]
            end += 1
        while tau >= k and start < lam:
            if (end - start) < (min_end - min_start):
                min_end, min_start = end, start
            tau -= m[start]
            start += 1
    if min_end > lam:  # fewer than k records total: return everything (engine refills)
        return 0, lam
    return min_start, min_end


def rank_left(sorted_arr: jax.Array, v: jax.Array) -> jax.Array:
    """``jnp.searchsorted(sorted_arr, v, side="left")`` for 1-D arrays, by a
    sort-merge rank: no gather, scatter or loop.

    One sort merges ``[v, sorted_arr]`` keyed on (value, tag), where query i
    carries tag i and every ``sorted_arr`` entry tag ``len(v)``, so a query
    sorts before an equal entry (``side="left"``).  A prefix count of the
    entries in merged order counts those strictly below each query, and a
    second sort on the tag brings the queries back to their own order.  The
    value order is ``lax.sort``'s total order, the one ``searchsorted``
    compares with.  Exact for any ``v``; equal to ``searchsorted`` whenever
    ``sorted_arr`` is nondecreasing in that order.
    """
    n = v.shape[0]
    keys = jnp.concatenate([v, sorted_arr])
    tag = jnp.concatenate([
        jnp.arange(n, dtype=jnp.int32),
        jnp.full(sorted_arr.shape, n, jnp.int32),
    ])
    _, tag = jax.lax.sort((keys, tag), num_keys=2)
    below = jnp.cumsum(tag == n, dtype=jnp.int32)  # entries at or before each slot
    _, below = jax.lax.sort((tag, below), num_keys=1)
    return below[:n]


class TwoProngResult(NamedTuple):
    start: jax.Array  # [] int32 inclusive
    end: jax.Array  # [] int32 exclusive
    expected_records: jax.Array  # [] f32


def two_prong_select(
    combined: jax.Array, k: jax.Array | int, records_per_block: int
) -> TwoProngResult:
    """TPU-native TWO-PRONG. jit-safe."""
    lam = combined.shape[0]
    m = combined * records_per_block
    c = jnp.concatenate([jnp.zeros((1,), m.dtype), jnp.cumsum(m)])  # [lam+1]
    k = jnp.asarray(k, dtype=m.dtype)
    # minimal end for each start: smallest e with c[e] >= c[i] + k
    targets = c[:-1] + k
    ends = rank_left(c, targets)  # [lam]
    starts = jnp.arange(lam, dtype=jnp.int32)
    feasible = ends <= lam
    lengths = jnp.where(feasible, ends - starts, jnp.iinfo(jnp.int32).max)
    best = jnp.argmin(lengths).astype(jnp.int32)  # first occurrence == smallest start
    any_feasible = jnp.any(feasible)
    start = jnp.where(any_feasible, best, 0)
    end = jnp.where(any_feasible, ends[best], lam)
    exp = c[end] - c[start]
    return TwoProngResult(start=start, end=end, expected_records=exp)


two_prong_select_jit = jax.jit(two_prong_select, static_argnums=(2,))

#: Batched TWO-PRONG: plan Q queries in one vectorized call (vmap of the
#: scalar planner; each row bit-identical to its single-query plan).
two_prong_select_batch = jax.jit(
    jax.vmap(two_prong_select, in_axes=(0, 0, None)), static_argnums=(2,)
)
