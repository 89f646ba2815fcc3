"""Tiered block storage: equivalence, placement, and residency-aware suites.

The contract under test (see ``src/repro/storage/tiers.py``): a
:class:`~repro.storage.tiers.TierStack` dropped in as the engine's block
cache returns *byte-identical* results to the flat-cache oracle under ANY
tier budgets and ANY placement policy — eviction pressure (demotion
cascades), drops, append invalidation (every tier evicts the dirtied tail),
and the device pipeline under a tiny tier-0 budget included.  Placement
behavior itself (admission / promotion / demotion / victim selection by
modeled io_time saved per byte) is asserted through the per-tier counters,
and the residency-aware layers on top — effective-cost §7.2 arbitration and
the admission controller's early resident-wave launch — get targeted
scenario tests.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.cost_model import make_cost_model
from repro.core.engine import NeedleTailEngine
from repro.core.multi_query import BatchQuery
from repro.data.block_store import Table, build_block_store
from repro.data.synthetic import make_clustered_table
from repro.storage import (
    CostAwarePolicy, RecencyPolicy, Tier, TierStack, make_tier_stack,
)

pytestmark = pytest.mark.serving

RPB = 64
NB = RPB * (4 * 4 + 2 * 4 + 1)  # slab bytes of the 4-dim/2-measure tables


def _make_table(kind: str, seed: int, n: int = 6_000) -> Table:
    rng = np.random.default_rng(seed)
    if kind == "clustered":
        return make_clustered_table(num_records=n, num_dims=4, density=0.15,
                                    seed=seed, mean_cluster=48)
    if kind == "uniform":
        return Table(
            dims=rng.integers(0, 3, (n, 4)).astype(np.int32),
            measures=rng.normal(size=(n, 2)).astype(np.float32),
            cards=np.asarray([3, 3, 3, 3]),
        )
    if kind == "skewed":
        dims = np.zeros((n, 4), np.int32)
        dims[: n // 10, 0] = 1
        dims[:, 1] = rng.integers(0, 2, n)
        dims[:, 2] = (np.arange(n) // RPB) % 3
        dims[:, 3] = rng.integers(0, 3, n)
        return Table(
            dims=dims,
            measures=rng.normal(size=(n, 2)).astype(np.float32),
            cards=np.asarray([2, 2, 3, 3]),
        )
    raise ValueError(kind)


_STORES: dict = {}


def _store(kind: str, seed: int):
    key = (kind, seed)
    if key not in _STORES:
        _STORES[key] = build_block_store(_make_table(kind, seed), RPB)
    return _STORES[key]


QUERY_POOL = [
    ([(0, 1)], 40, "and"),
    ([(0, 1), (1, 1)], 120, "and"),
    ([(1, 1), (2, 1)], 60, "or"),
    ([(2, 0)], 25, "and"),
    ([(0, 1), (2, 1), (3, 1)], 200, "and"),
    ([(3, 1), (1, 0)], 90, "or"),
]


def _queries(spec) -> list[BatchQuery]:
    return [BatchQuery(p, k, op) for (p, k, op) in spec]


def _assert_result_equal(a, b):
    np.testing.assert_array_equal(a.record_block, b.record_block)
    np.testing.assert_array_equal(a.record_row, b.record_row)
    np.testing.assert_array_equal(a.measures, b.measures)
    np.testing.assert_array_equal(a.blocks_fetched, b.blocks_fetched)
    assert a.plan_rounds == b.plan_rounds
    assert a.algo == b.algo


def _assert_batch_equal(a, b):
    assert len(a.results) == len(b.results)
    for ra, rb in zip(a.results, b.results):
        _assert_result_equal(ra, rb)


def _stack_config(name: str) -> TierStack:
    """Named tier configs the equivalence property sweeps over."""
    # budgets are real slab bytes; the cost presets keep their default
    # 256 KB block (at the test's 1.6 KB slabs the hbm DMA-issue latency
    # would exceed dram's access latency and honestly invert the ladder)
    if name == "roomy":  # everything fits everywhere
        return make_tier_stack(None, None)
    if name == "tiny_hbm":  # tier-0 pressure: cost-aware spill to dram
        return make_tier_stack(3 * NB, None)
    if name == "tiny_both":  # total budget under the working set: drops
        return make_tier_stack(2 * NB, 3 * NB)
    if name == "recency":  # pure recency: every block enters tier 0, cascades
        return make_tier_stack(3 * NB, 5 * NB, policy=RecencyPolicy())
    if name == "device_fill":  # tier-0 filled through the Pallas union gather
        return make_tier_stack(4 * NB, None, device_fill=True)
    raise ValueError(name)


# ---------------------------------------------------------------------------
# Satellite: the `dram` preset and the preset cost ladder.
# ---------------------------------------------------------------------------
def test_cost_model_preset_consistency():
    """Every preset is self-consistent and the tier ladder is strict:
    hbm < dram < ici < ssd < hdd on far_cost AND on a scattered fetch."""
    ladder = ["hbm", "dram", "ici", "ssd", "hdd"]
    scattered = np.asarray([0, 97, 311, 1024, 4097])
    costs = []
    for kind in ladder:
        cm = make_cost_model(kind)
        assert cm.name == kind
        assert 0 < cm.seq_cost <= cm.far_cost
        assert cm.first_block_cost > 0 and cm.max_dist >= 1
        # the curve interpolates seq -> far and never exceeds the far seek
        d = np.arange(1, cm.max_dist + 1)
        near = np.asarray(cm.curve(d), dtype=np.float64)
        assert np.all(np.diff(near) >= -1e-12)  # non-decreasing in distance
        assert near[0] == pytest.approx(cm.seq_cost)
        assert np.all(near <= cm.far_cost + 1e-12)
        assert cm.rand_io(0, cm.max_dist + 10) == pytest.approx(cm.far_cost)
        assert cm.io_time([]) == 0.0
        assert cm.io_time([5]) == pytest.approx(cm.first_block_cost)
        costs.append((cm.far_cost, cm.io_time(scattered)))
    fars, ios = zip(*costs)
    assert list(fars) == sorted(fars) and len(set(fars)) == len(fars)
    assert list(ios) == sorted(ios) and len(set(ios)) == len(ios)


# ---------------------------------------------------------------------------
# Property: flat-cache oracle == every tiered config, per query and per
# batch, across layouts / ops / algos — including warm repeats and pressure.
# ---------------------------------------------------------------------------
@settings(max_examples=8, deadline=None)
@given(
    st.sampled_from(("clustered", "uniform", "skewed")),
    st.integers(0, 2),
    st.sampled_from(("threshold", "two_prong", "auto")),
    st.sampled_from(("roomy", "tiny_hbm", "tiny_both", "recency", "device_fill")),
    st.lists(st.sampled_from(QUERY_POOL), min_size=1, max_size=4),
)
def test_tiered_equivalence_to_flat_oracle(kind, seed, algo, config, spec):
    store = _store(kind, seed)
    queries = _queries(spec)
    ref = NeedleTailEngine(store, cache_bytes=0)  # the flat-cache oracle
    ref_batch = ref.any_k_batch(queries, algo=algo)
    ref_seq = [ref.any_k(q.predicates, q.k, op=q.op, algo=algo) for q in queries]

    stack = _stack_config(config)
    eng = NeedleTailEngine(store, tiers=stack)
    cold = eng.any_k_batch(queries, algo=algo)
    _assert_batch_equal(cold, ref_batch)
    assert cold.tier_stats is not None  # the per-tier ledger is threaded
    for q, r in zip(queries, ref_seq):
        _assert_result_equal(eng.any_k(q.predicates, q.k, op=q.op, algo=algo), r)

    warm = eng.any_k_batch(queries, algo=algo)
    _assert_batch_equal(warm, ref_batch)
    uniq = int(cold.unique_blocks_fetched.size)
    if config in ("roomy", "tiny_hbm", "device_fill"):
        # an unbounded host tier holds the whole working set: the warm wave
        # is served from tiers 0-1 with ZERO backing-store reads
        assert warm.store_blocks_fetched == 0
        assert stack.stats.evictions == 0  # demote, never drop
    if config == "recency" and uniq > 3:
        # recency admits everything to tier 0: pressure MUST cascade down
        tc = stack.tier_counters()
        assert tc["hbm.demotions_out"] > 0
        assert tc["dram.demotions_in"] == tc["hbm.demotions_out"]
    # a budget-constrained third pass stays byte-identical regardless
    _assert_batch_equal(eng.any_k_batch(queries, algo=algo), ref_batch)


# ---------------------------------------------------------------------------
# Property: append invalidation evicts the dirtied tail from EVERY tier.
# ---------------------------------------------------------------------------
@settings(max_examples=6, deadline=None)
@given(
    st.sampled_from(("clustered", "uniform")),
    st.integers(0, 2),
    st.integers(1, 400),
    st.lists(st.sampled_from(QUERY_POOL), min_size=1, max_size=3),
)
def test_append_invalidates_all_tiers(kind, seed, n_extra, spec):
    base = _make_table(kind, seed)
    extra_full = _make_table(kind, seed + 100)
    extra = Table(
        dims=extra_full.dims[:n_extra],
        measures=extra_full.measures[:n_extra],
        cards=base.cards,
    )
    store = build_block_store(base, RPB)
    stack = make_tier_stack(4 * NB, None)
    eng = NeedleTailEngine(store, tiers=stack)
    queries = _queries(spec)
    eng.any_k_batch(queries, algo="auto")
    # force the trailing partial block resident in BOTH tiers' reach
    eng.block_cache.ensure(store, np.arange(store.num_blocks))

    first_touched = store.num_records // RPB
    grown = eng.append(extra)
    for b in range(first_touched, grown.num_blocks):
        for tier in stack.tiers:  # a stale copy in ANY tier would be a bug
            assert b not in tier
    assert stack.stats.invalidations > 0

    ref = NeedleTailEngine(grown, cache_bytes=0)
    for algo in ("threshold", "auto"):
        _assert_batch_equal(
            eng.any_k_batch(queries, algo=algo),
            ref.any_k_batch(queries, algo=algo),
        )


@pytest.mark.parametrize("mode", ["flat", "tiered"])
def test_append_rereads_not_counted_as_misses(mode):
    """Re-reading blocks evicted by append invalidation books under
    ``invalidation_rereads``, NOT ``misses`` — a warm cache that just
    absorbed an append must not look cold to the cost model / bench gates
    (counter-drift regression guard, flat LRU and tier stack alike)."""
    base = _make_table("clustered", 3)
    extra_full = _make_table("clustered", 103)
    extra = Table(dims=extra_full.dims[: 2 * RPB],
                  measures=extra_full.measures[: 2 * RPB],
                  cards=base.cards)
    store = build_block_store(base, RPB)
    if mode == "tiered":
        eng = NeedleTailEngine(store, tiers=make_tier_stack(4 * NB, None))
    else:
        eng = NeedleTailEngine(store)
    cache = eng.block_cache
    cache.ensure(store, np.arange(store.num_blocks))

    first_touched = store.num_records // RPB
    grown = eng.append(extra)
    touched = np.arange(first_touched, grown.num_blocks)

    misses0 = cache.stats.misses
    rereads0 = cache.stats.invalidation_rereads
    cache.ensure(grown, touched)
    assert cache.stats.invalidation_rereads - rereads0 == touched.size
    assert cache.stats.misses == misses0, (
        "append-invalidation re-reads inflated the cold-miss counter")

    # one-shot marks: the blocks are resident again, a repeat is pure hits
    misses1, rereads1 = cache.stats.misses, cache.stats.invalidation_rereads
    cache.ensure(grown, touched)
    assert (cache.stats.misses, cache.stats.invalidation_rereads) \
        == (misses1, rereads1)

    ref = NeedleTailEngine(grown, cache_bytes=0)
    queries = _queries(QUERY_POOL[:3])
    _assert_batch_equal(eng.any_k_batch(queries, algo="auto"),
                        ref.any_k_batch(queries, algo="auto"))


# ---------------------------------------------------------------------------
# Device pipeline under a tiny tier-0 budget: byte-identity + transfer ledger.
# ---------------------------------------------------------------------------
@pytest.mark.device
def test_device_pipeline_rounds_on_tiered_storage():
    store = _store("clustered", 1)
    queries = _queries(QUERY_POOL[:4])
    ref = NeedleTailEngine(store, cache_bytes=0)
    ref_batch = ref.any_k_batch(queries, algo="auto")

    stack = make_tier_stack(2 * NB, None, device_fill=True)
    eng = NeedleTailEngine(store, tiers=stack)
    cold = eng.any_k_batch(queries, algo="auto", device=True)
    _assert_batch_equal(cold, ref_batch)
    assert cold.device_transfers <= cold.rounds + 1  # the ≤1/round ledger
    warm = eng.any_k_batch(queries, algo="auto", device=True)
    _assert_batch_equal(warm, ref_batch)
    assert warm.store_blocks_fetched == 0  # served from tiers 0-1
    assert warm.device_transfers <= warm.rounds + 1
    assert stack.stats.evictions == 0  # tier-0 pressure demoted, not dropped
    tc = stack.tier_counters()
    assert tc["hbm.demotions_out"] > 0 and tc["dram.demotions_in"] > 0


def test_get_device_serves_tier0_residency():
    store = _store("clustered", 0)
    stack = make_tier_stack(None, None, device_fill=True)
    ids = np.asarray([0, 3, 7, 2])
    dd, dm, dv = stack.get_device(store, ids)
    bd, bm, bv = store.fetch(ids)
    np.testing.assert_array_equal(np.asarray(dd), bd)
    np.testing.assert_array_equal(np.asarray(dm), bm)
    np.testing.assert_array_equal(np.asarray(dv), bv)
    assert all(int(b) in stack.tiers[0] for b in ids)
    # device gathers are logical accesses: they feed the hit ledger and the
    # policy's frequency scores (promotion eligibility, victim protection)
    h0 = stack.tiers[0].stats.hits
    stack.get_device(store, ids)
    assert stack.tiers[0].stats.hits == h0 + ids.size
    assert all(stack.accesses(int(b)) == 2 for b in ids)


@pytest.mark.parametrize("rpb", [16, 64, 128])
@pytest.mark.parametrize("ids", [[0], [5, 2], [93, 0, 7], [4, 4, 1, 93, 8], list(range(9))])
def test_lane_dense_store_device_paths_match_host(rpb, ids):
    """The device copy is lane-dense (record axis minor) and every device
    read path — ``fetch_device``, ``TierStack.get_device`` and host gathers
    of tier-0 slabs — returns the host ``fetch`` bytes, including repeated
    ids, union sizes on both sides of a power-of-two bucket, and the last
    (partial) block whose row validity is derived on the device."""
    table = _make_table("clustered", 4, n=6_001)  # a partial last block
    store = build_block_store(table, rpb)
    lam, r, s = store.num_blocks, table.dims.shape[1], table.measures.shape[1]
    assert store.dims_dev.shape == (lam, r, rpb)
    assert store.meas_dev.shape == (lam, s, rpb)
    ids = np.asarray([b % lam if b < 93 else lam - 1 for b in ids])
    want = store.fetch(ids)
    for i in np.flatnonzero(ids == lam - 1):
        assert 0 < want[2][i].sum() == table.num_records - (lam - 1) * rpb < rpb
    stack = make_tier_stack(None, None, device_fill=True)
    for got in (store.fetch_device(ids), stack.get_device(store, ids),
                stack.get_many(store, ids)):
        for g, w in zip(got, want):
            g = np.asarray(g)
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(g, w)
    # tier-0 residency holds lane-dense per-block slabs
    entry = stack.tiers[0].peek(int(ids[0]))
    assert entry[0].shape == (r, rpb) and entry[1].shape == (s, rpb)


def test_host_gather_of_device_slab_memoizes_one_download():
    """A device-tier resident serves host gathers through a memoized host
    mirror: one device→host download per residency, not one per access —
    and the mirror dies with the slab."""
    store = _store("clustered", 0)
    stack = make_tier_stack(None, None, device_fill=True)
    ids = np.asarray([1, 4])
    first = stack.get_many(store, ids)
    ref = store.fetch(ids)
    for got, want in zip(first, ref):
        np.testing.assert_array_equal(got, want)
    m1 = stack.tiers[0].host_view(1)
    assert m1 is not None
    again = stack.get_many(store, ids)
    for got, want in zip(again, ref):
        np.testing.assert_array_equal(got, want)
    assert stack.tiers[0].host_view(1) is m1  # same mirror object: no re-download
    stack.invalidate([1])
    assert stack.tiers[0]._host_mirror.get(1) is None


# ---------------------------------------------------------------------------
# Placement mechanics: cost-aware admission / promotion / victim selection.
# ---------------------------------------------------------------------------
def test_cost_aware_promotion_displaces_weakest_incumbent():
    """A hot lower-tier block out-scores a cold tier-0 incumbent (same Δcost
    and slab size, so the io_time-saved-per-byte comparison reduces to
    access frequency) and takes its slot; the incumbent demotes, not drops."""
    store = _store("uniform", 0)
    stack = make_tier_stack(2 * NB, None,
                            policy=CostAwarePolicy(promote_after=2))
    # blocks 0,1 fill tier 0 (admitted to free fast capacity)...
    stack.get_many(store, np.asarray([0, 1]))
    assert 0 in stack.tiers[0] and 1 in stack.tiers[0]
    # ...block 2 admits to dram (tier 0 full), then gets hot
    stack.get_many(store, np.asarray([2]))
    assert 2 in stack.tiers[1]
    for _ in range(4):
        stack.get_many(store, np.asarray([2]))
    assert 2 in stack.tiers[0]  # promoted past the cold incumbents
    assert (0 in stack.tiers[1]) or (1 in stack.tiers[1])  # demoted, resident
    assert stack.stats.evictions == 0
    tc = stack.tier_counters()
    assert tc["hbm.promotions_in"] == 1 and tc["hbm.demotions_out"] == 1


def test_demotion_into_a_too_small_tier_is_counted_as_a_drop():
    """A 'demotion' whose every lower tier is too small for the slab leaves
    the stack — the ledger must record an eviction, not a phantom arrival
    (the demote-not-drop CI guard trusts these counters)."""
    store = _store("uniform", 0)
    stack = make_tier_stack(2 * NB, NB // 2, policy=RecencyPolicy())
    stack.get_many(store, np.asarray([0, 1]))
    ref = store.fetch(np.asarray([0, 1, 2]))
    out = stack.get_many(store, np.asarray([2]))  # displaces the tier-0 LRU
    np.testing.assert_array_equal(out[0], ref[0][2:])
    tc = stack.tier_counters()
    assert stack.stats.evictions == 1  # the displaced block really dropped
    assert tc["hbm.evictions"] == 1 and tc["hbm.demotions_out"] == 0
    assert tc["dram.demotions_in"] == 0 and len(stack.tiers[1]) == 0
    # and the data path stays exact regardless
    again = stack.get_many(store, np.asarray([0, 1, 2]))
    for got, want in zip(again, ref):
        np.testing.assert_array_equal(got, want)


def test_promotion_into_a_too_small_tier_is_not_ledgered():
    """A policy without its own fits_at_all guard (pure recency) promoting
    into a tier that cannot hold one slab must be a no-op — not a pop and
    re-insert into the SAME tier recorded as a phantom promotion."""
    store = _store("uniform", 0)
    stack = make_tier_stack(NB // 2, None, policy=RecencyPolicy())
    ref = store.fetch(np.asarray([0, 1]))
    for _ in range(3):
        out = stack.get_many(store, np.asarray([0, 1]))
        for got, want in zip(out, ref):
            np.testing.assert_array_equal(got, want)
    tc = stack.tier_counters()
    assert len(stack.tiers[0]) == 0  # nothing can ever reside in tier 0
    assert tc["dram.promotions_in"] == 0 and tc["hbm.promotions_in"] == 0
    assert tc["dram.hits"] == 4  # the warm repeats really were hits


def test_inverted_cost_ladder_never_promotes():
    """A 'fast' tier that is actually slower than the level below offers no
    io_time saving — the cost-aware arbiter must refuse to promote into it
    and must not admit fresh blocks there."""
    slow_top = TierStack(
        tiers=[
            Tier("slow", 4 * NB, make_cost_model("hdd", NB)),
            Tier("fast", None, make_cost_model("dram", NB)),
        ],
        backing=make_cost_model("hdd", NB),
        policy=CostAwarePolicy(promote_after=1),
    )
    store = _store("uniform", 1)
    for _ in range(3):
        slow_top.get_many(store, np.asarray([0, 1, 2]))
    tc = slow_top.tier_counters()
    assert tc["slow.promotions_in"] == 0 and tc["slow.admissions"] == 0
    assert len(slow_top.tiers[0]) == 0 and len(slow_top.tiers[1]) == 3


def test_effective_io_time_prices_by_residency():
    store = _store("uniform", 2)
    stack = make_tier_stack(2 * NB, None)
    backing = stack.backing
    ids = np.asarray([0, 1, 2, 3])
    cold = stack.effective_io_time(ids)
    assert cold == pytest.approx(backing.io_time(ids))
    stack.ensure(store, ids)
    warm = stack.effective_io_time(ids)
    # resident blocks price at µs-scale tier models, not the ms-scale store
    assert warm < cold / 100
    # a disjoint cold set still prices at the backing model
    assert stack.effective_io_time([10, 11]) == pytest.approx(
        backing.io_time([10, 11])
    )


def _qerr(got: float, want: float) -> float:
    q = got / want
    return max(q, 1.0 / q)


def test_effective_io_time_calibrated_mixed_residency():
    """Calibration refits BOTH components effective_io_time composes: a
    mixed warm/cold set prices as a fitted-dram pass over the residents plus
    a fitted-backing pass over the misses — dedup and the ``backing=``
    override behave exactly as on the preset path."""
    from repro.storage import SyntheticTimingBackend

    store = _store("uniform", 3)
    # hbm budget 0: nothing ever fits tier 0, residents land in dram
    stack = make_tier_stack(0, None, backing="ssd", block_bytes=NB)
    truth_ssd = make_cost_model("hdd", NB)  # the "ssd" really seeks like HDD
    truth_dram = make_cost_model("dram", 5 * NB)  # host copies 5x slower
    fitted = stack.calibrate(
        SyntheticTimingBackend({"ssd": truth_ssd, "dram": truth_dram}))
    assert stack.backing is fitted["ssd"]
    assert stack.tiers[1].cost is fitted["dram"]
    stack.ensure(store, np.asarray([0, 1]))
    assert list(stack.residency_tier(np.asarray([0, 1, 7, 11]))) == [1, 1, 2, 2]
    mixed = stack.effective_io_time([0, 1, 7, 11])
    expect = fitted["dram"].io_time([0, 1]) + fitted["ssd"].io_time([7, 11])
    assert mixed == pytest.approx(expect)
    # the fitted components track the deviating truth, not the old presets
    assert _qerr(fitted["dram"].io_time([0, 1]), truth_dram.io_time([0, 1])) < 1.5
    assert _qerr(fitted["ssd"].io_time([7, 11]), truth_ssd.io_time([7, 11])) < 1.5
    # dedup survives the calibrated mixed-residency path, in any order
    assert stack.effective_io_time([0, 0, 1, 7, 7, 11]) == pytest.approx(mixed)
    assert stack.effective_io_time([1, 0, 11, 7, 1]) == pytest.approx(mixed)
    # `backing=` override prices the cold run under the caller's model
    slow = make_cost_model("hdd", NB)
    assert stack.effective_io_time([7, 11], backing=slow) == pytest.approx(
        slow.io_time([7, 11]))


def test_effective_io_time_applies_ledger_corrections():
    """Between recalibrations, the plan ledger's committed q-error
    correction scales each level's component — misses under the backing's
    multiplier, residents under their own tier's, an override under the
    override level's (none recorded → uncorrected)."""
    from repro.core.plan_ledger import PlanLedger

    store = _store("uniform", 4)
    stack = make_tier_stack(0, None, backing="hdd", block_bytes=NB)
    stack.ledger = PlanLedger()
    ids = [3, 4, 9]
    base = stack.effective_io_time(ids)
    stack.ledger.record("placement", "hdd", 1.0, 4.0)
    corr = stack.ledger.correction("hdd")
    assert corr == pytest.approx(4.0)
    assert stack.effective_io_time(ids) == pytest.approx(base * corr)
    stack.ensure(store, np.asarray([3]))
    # the demand fetch itself recorded a (wall-clock) placement observation,
    # so re-read the committed multiplier before composing the expectation
    corr2 = stack.ledger.correction("hdd")
    expect = (stack.tiers[1].cost.io_time([3])
              + stack.backing.io_time([4, 9]) * corr2)
    assert stack.effective_io_time(ids) == pytest.approx(expect)
    ssd = make_cost_model("ssd", NB)
    assert stack.effective_io_time([4, 9], backing=ssd) == pytest.approx(
        ssd.io_time([4, 9]))


def test_effective_io_time_prices_peer_hop_with_fitted_ici():
    """A peer-resident block prices at the interconnect hop, and a model
    fitted from measured link timings (4x slower than the ``ici`` preset)
    overrides the preset through ``make_peer_stack(ici_cost=...)``."""
    from repro.storage import (
        PeerGroup, SyntheticTimingBackend, calibrate_model, make_peer_stack,
    )

    store = _store("uniform", 5)
    truth_ici = make_cost_model("ici", 4 * NB)
    fitted_ici = calibrate_model(
        SyntheticTimingBackend({"ici": truth_ici}), "ici",
        base=make_cost_model("ici", NB))
    group = PeerGroup(store, 2)
    local = make_peer_stack(group, 0, block_bytes=NB, ici_cost=fitted_ici)
    remote = make_peer_stack(group, 1, block_bytes=NB)
    remote.get_many(store, np.asarray([42]))  # shard 1 owns block 42
    peer_idx = local.tiers.index(local.peer_tier)
    assert local.residency_tier(np.asarray([42]))[0] == peer_idx
    got = local.effective_io_time([42])
    assert got == pytest.approx(fitted_ici.io_time([42]))
    assert _qerr(got, truth_ici.io_time([42])) < 1.5
    # the hop is priced dearer than the preset assumed, cheaper than a seek
    assert got > make_cost_model("ici", NB).io_time([42])
    assert got < local.backing.io_time([42])


def test_residency_aware_auto_prefers_resident_plan():
    """The §7.2 arbitration flip: cold, THRESHOLD's two far blocks beat the
    13-block TWO-PRONG window; with the window resident in tiers and the
    effective cost model in play, the window wins."""
    n_blocks = 60
    dims = np.zeros((n_blocks * RPB, 1), np.int32)
    for b in (0, 50):  # two fully-dense far-apart blocks
        dims[b * RPB:(b + 1) * RPB] = 1
    for b in range(10, 31):  # a long half-dense run: 10 matching rows each
        dims[b * RPB: b * RPB + 10] = 1
    table = Table(
        dims=dims,
        measures=np.arange(dims.shape[0], dtype=np.float32)[:, None],
        cards=np.asarray([2]),
    )
    store = build_block_store(table, RPB)
    k = 128  # needs density mass 2.0: {0, 50} or ~13 blocks of the run

    flat = NeedleTailEngine(store)  # backing-model arbitration (the paper)
    plan_flat, algo_flat = flat.plan([(0, 1)], k, algo="auto")
    assert algo_flat == "threshold" and set(plan_flat) == {0, 50}

    stack = make_tier_stack(None, None)
    aware = NeedleTailEngine(store, tiers=stack, residency_aware=True)
    stack.ensure(store, np.arange(10, 31))  # the run is resident, {0,50} cold
    plan_aware, algo_aware = aware.plan([(0, 1)], k, algo="auto")
    assert algo_aware == "two_prong"  # the resident window beats 2 cold seeks
    assert set(plan_aware) <= set(range(10, 31))
    # the chosen plan still answers the query: the window really holds >= k
    r = aware.any_k([(0, 1)], k, algo="auto")
    assert r.num_records >= k
    assert np.all(table.dims[r.record_block * RPB + r.record_row, 0] == 1)


# ---------------------------------------------------------------------------
# Residency-aware admission: fully-resident waves launch before the SLO.
# ---------------------------------------------------------------------------
class _SimClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_admission_launches_resident_wave_early():
    from repro.serving.admission import AdmissionController, AdmissionPolicy
    from repro.storage.residency import make_residency_probe

    store = _store("clustered", 0)
    stack = make_tier_stack(None, None)
    eng = NeedleTailEngine(store, tiers=stack)
    hot = _queries(QUERY_POOL[:3])
    eng.any_k_batch(hot, algo="auto")  # warm the plan memo + tiers

    clk = _SimClock()
    adm = AdmissionController(
        AdmissionPolicy(slo_s=100.0, max_wave=8),
        clock=clk,
        residency_probe=make_residency_probe(eng),
    )
    for q in hot:
        adm.submit(q)
    wave = adm.poll()  # SLO is an eternity away; residency launches it NOW
    assert wave is not None and len(wave) == 3
    assert adm.stats.resident_waves == 1
    batch = eng.any_k_batch(wave, algo="auto")
    assert batch.store_blocks_fetched == 0  # the promised zero-I/O wave

    # a never-seen template is not memoized: the probe refuses, the wave
    # accumulates until its deadline like any cold wave
    adm.submit(BatchQuery([(1, 1), (3, 1)], 33, "and"))
    assert adm.poll() is None
    clk.t = 200.0
    wave = adm.poll()
    assert wave is not None and adm.stats.deadline_waves == 1


def test_residency_probe_serves_mesh_attached_engines():
    """A mesh-attached engine's waves feed the sharded-THRESHOLD memo, not
    the host sorted-order memo — the probe must peek that one instead."""
    import jax

    from repro.storage.residency import wave_is_resident

    store = _store("clustered", 1)
    stack = make_tier_stack(None, None)
    eng = NeedleTailEngine(store, tiers=stack)
    eng.attach_mesh(jax.make_mesh((1,), ("data",)))
    hot = _queries(QUERY_POOL[:2])
    assert not wave_is_resident(eng, hot)  # nothing memoized yet
    eng.any_k_batch(hot, algo="auto")  # sharded plan wave warms memo + tiers
    assert eng.plan_cache.stats.threshold_misses == 0  # host memo untouched
    assert wave_is_resident(eng, hot)
    batch = eng.any_k_batch(hot, algo="auto")
    assert batch.store_blocks_fetched == 0


def test_serve_engine_residency_wiring():
    """ServeEngine(exemplar_residency=True) installs the probe on its
    controller and last_wave_stats carries the per-tier placement ledger."""
    import itertools

    from repro.serving.admission import AdmissionController, AdmissionPolicy
    from repro.serving.engine import ServeEngine

    store = _store("clustered", 2)
    stack = make_tier_stack(None, None)
    eng = NeedleTailEngine(store, tiers=stack)
    hot = _queries(QUERY_POOL[:2])
    eng.any_k_batch(hot, algo="auto")

    clk = _SimClock()
    serve = ServeEngine.__new__(ServeEngine)  # no LM needed for exemplars
    serve.max_slots = 8
    serve.exemplar_residency = True
    serve.exemplar_admission = AdmissionController(
        AdmissionPolicy(slo_s=100.0, max_wave=8), clock=clk
    )
    serve._rid = itertools.count()
    for p, k, op in QUERY_POOL[:2]:
        serve.submit_exemplar_request(p, k, op)
    done = serve.pump_exemplar_requests(eng)  # far SLO: residency launches
    assert len(done) == 2 and all(r.done for r in done)
    assert serve.exemplar_admission.stats.resident_waves == 1
    stats = serve.last_wave_stats
    assert stats["store_blocks_fetched"] == 0
    assert stats["tiers"] is not None
    assert stats["tiers"]["hbm.hits"] + stats["tiers"]["dram.hits"] > 0


# ---------------------------------------------------------------------------
# The sharded fetch path: ici-priced remote fetches through the tier stack.
# ---------------------------------------------------------------------------
def test_distributed_fetch_prices_remote_blocks_with_ici():
    import jax

    from repro.core.sharded import DistributedAnyK

    store = _store("clustered", 1)
    stack = make_tier_stack(None, None)
    eng = NeedleTailEngine(store, tiers=stack)
    mesh = jax.make_mesh((1,), ("data",))
    dist = DistributedAnyK(
        mesh, records_per_block=RPB, candidates=store.num_blocks,
        block_cache=eng.block_cache,
    )
    assert dist.remote_cost.name == "ici"
    comb = eng.combined_density([(0, 1)])
    plan = dist.threshold_plan(np.asarray(comb, np.float32), 64.0)
    ids, bd, bm, bv = dist.fetch_plan(store, plan)
    ref = store.fetch(ids)
    np.testing.assert_array_equal(bd, ref[0])
    np.testing.assert_array_equal(bm, ref[1])
    np.testing.assert_array_equal(bv, ref[2])
    cold_io = dist.last_fetch_io_s
    assert cold_io == pytest.approx(dist.remote_cost.io_time(ids))
    dist.fetch_plan(store, plan)  # now tier-resident: effective price drops
    assert dist.last_fetch_io_s < cold_io
    assert all(int(b) in eng.block_cache for b in ids)
