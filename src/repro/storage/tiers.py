"""Tiered block storage: HBM device buffers → host DRAM → backing store.

The paper wins 4x on HDDs and 9x on SSDs from the *same* algorithm because
the cost model changes which blocks are promising.  This module applies that
observation to the serving stack's own memory hierarchy: instead of one flat
engine-lifetime ``BlockLRUCache`` in front of the ``BlockStore``, a
:class:`TierStack` layers byte-budgeted tiers — device-resident HBM slabs on
top, a host-DRAM tier below, the backing store at the bottom — each tier
priced by its own :class:`~repro.core.cost_model.CostModel` preset
(``hbm`` / ``dram`` / whatever the store sits on), with a pluggable
:class:`~repro.storage.policy.PlacementPolicy` arbitrating admission,
promotion, demotion, and victim selection by modeled **io_time saved per
byte** rather than pure recency.

Drop-in contract
----------------
``TierStack`` implements the same surface the engine-lifetime LRU exposes —
``get_many`` / ``ensure`` / ``invalidate`` / ``clear`` / ``__contains__`` /
``__len__`` / ``stats`` / ``fetch_log`` — so it slots in as
``NeedleTailEngine.block_cache`` unchanged and every fetch path routes
through it: ``any_k``, the ``run_batch`` host and device pipelines
(``_execute_wave`` calls ``ensure`` + ``get_many``), and
:meth:`repro.core.sharded.DistributedAnyK.fetch_plan` (which takes the
engine's ``block_cache`` by reference).

**Byte-identity guarantee** (inherited from the flat LRU and locked down by
``tests/test_tiering.py``): for any tier budgets, any placement policy, and
any sequence of ``get_many`` / ``ensure`` / ``invalidate`` calls,
``get_many(store, ids)`` returns slabs byte-identical to
``store.fetch(ids)``.  Placement changes the physical I/O schedule — which
medium a block is served from — never the data.

Tier 0 and the device fill path
-------------------------------
A tier constructed with ``device=True`` holds its slabs as **jax Arrays**
(device buffers) in the store's lane-dense device layout — ``dims [r, R]``,
``meas [s, R]``, ``valid [R]`` per block, the record axis minor so TPU tiling
pads nothing.  Its fill path is :meth:`repro.data.block_store.BlockStore.
gather_device` — the one-launch Pallas union gather — when ``device_fill`` is
enabled (auto: on TPU backends; force ``True`` to exercise the kernel in
interpret mode), else a host fetch + upload.  Serving a host gather from a
device slab downloads it ONCE per residency — the download is memoized as a
host mirror beside the device buffer (host memory, outside the tier's
device byte budget) and performed under
``jax.transfer_guard_device_to_host("allow")`` so the device pipeline's
stray-transfer probe stays meaningful.  The ``run_batch`` loops — device
pipeline included — mask records on the host and therefore consume host
slabs via ``get_many``; ``get_device`` is the transfer-free surface for
*device-side* slab consumers (e.g. exemplar measures feeding an LM).

Invalidation contract
---------------------
Identical to the flat LRU's: the append path reports exactly the dirtied
tail block ids and :meth:`TierStack.invalidate` evicts them from **every**
tier (a stale tier-0 copy is as wrong as a stale host copy); anything that
swaps the store wholesale calls :meth:`TierStack.clear`.

Cost accounting
---------------
:meth:`TierStack.effective_io_time` prices a block set by *where it is
resident*: each tier's ids are costed as one §4.1 ascending pass under that
tier's model, misses under the backing model.  This is the "effective tier
cost" the residency-aware planner (``NeedleTailEngine(residency_aware=True)``)
feeds the §7.2 auto arbitration — a tier-0-resident sparse plan can beat a
cold dense one.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import TYPE_CHECKING, Iterable, Sequence

import numpy as np

from repro.core.block_cache import CacheStats
from repro.core.cost_model import CostModel, make_cost_model
from repro.storage.policy import CostAwarePolicy, PlacementPolicy

if TYPE_CHECKING:  # pragma: no cover - typing only
    from repro.data.block_store import BlockStore


@dataclasses.dataclass
class TierStats:
    """Per-tier placement counters (monotonic except the two gauges)."""

    hits: int = 0  # gathers served by this tier
    admissions: int = 0  # fresh store reads admitted here
    promotions_in: int = 0  # blocks moved up into this tier
    demotions_in: int = 0  # blocks displaced down into this tier
    demotions_out: int = 0  # residents displaced down out of this tier
    evictions: int = 0  # residents dropped out of the stack from here
    invalidations: int = 0  # residents evicted by append invalidation
    bytes_cached: int = 0  # gauge
    blocks_cached: int = 0  # gauge

    def snapshot(self) -> dict:
        return dataclasses.asdict(self)


class Tier:
    """One byte-budgeted level of the hierarchy.

    Parameters
    ----------
    name : str
        Display/counter key (``"hbm"``, ``"dram"``, ...).
    capacity_bytes : int | None
        Byte budget; ``None`` is unbounded.  A slab larger than the whole
        budget skips the tier (it is placed at the demotion target instead).
    cost : CostModel
        The preset this tier prices its residents with
        (:meth:`TierStack.effective_io_time`).
    device : bool
        ``True`` holds slabs as lane-dense jax Arrays (device buffers) and
        fills from :meth:`~repro.data.block_store.BlockStore.gather_device`.
    """

    def __init__(
        self,
        name: str,
        capacity_bytes: int | None,
        cost: CostModel,
        device: bool = False,
    ):
        self.name = name
        self.capacity_bytes = capacity_bytes
        self.cost = cost
        self.device = device
        self.stats = TierStats()
        # bytes promised to in-flight admissions of the current miss batch,
        # so sequential admit_tier decisions see the tier filling up
        self.reserved_bytes = 0
        # block id -> (dims, meas, valid, nbytes); arrays are np (host tier)
        # or jax (device tier), always copies/owned buffers, never store views
        self._slabs: "OrderedDict[int, tuple]" = OrderedDict()
        # device tiers only: lazily-memoized host views of resident slabs,
        # so repeated HOST gathers of a tier-0 hit pay the device→host
        # download once, not per access.  Host memory, deliberately outside
        # the tier's byte budget (which models the device capacity); dropped
        # with the slab on pop/clear.
        self._host_mirror: dict[int, tuple] = {}

    # ----------------------------------------------------------------- state
    def __contains__(self, block_id: int) -> bool:
        return int(block_id) in self._slabs

    def __len__(self) -> int:
        return len(self._slabs)

    def block_ids(self) -> Iterable[int]:
        """Resident ids in LRU order (least recently used first)."""
        return self._slabs.keys()

    def slab_nbytes(self, block_id: int) -> int | None:
        entry = self._slabs.get(int(block_id))
        return entry[3] if entry is not None else None

    def has_room(self, nbytes: int) -> bool:
        if self.capacity_bytes is None:
            return True
        return (
            self.stats.bytes_cached + self.reserved_bytes + nbytes
            <= self.capacity_bytes
        )

    def fits_at_all(self, nbytes: int) -> bool:
        """Whether a slab of `nbytes` could ever reside here."""
        return self.capacity_bytes is None or nbytes <= self.capacity_bytes

    # --------------------------------------------------------------- mutate
    def touch(self, block_id: int) -> None:
        self._slabs.move_to_end(int(block_id))

    def peek(self, block_id: int):
        return self._slabs.get(int(block_id))

    def put(self, block_id: int, slab: tuple) -> None:
        """Insert an owned slab tuple ``(dims, meas, valid, nbytes)``.  The
        caller (TierStack) is responsible for having made room."""
        self._slabs[int(block_id)] = slab
        self.stats.bytes_cached += slab[3]
        self.stats.blocks_cached = len(self._slabs)

    def pop(self, block_id: int):
        entry = self._slabs.pop(int(block_id), None)
        if entry is not None:
            self._host_mirror.pop(int(block_id), None)
            self.stats.bytes_cached -= entry[3]
            self.stats.blocks_cached = len(self._slabs)
        return entry

    def pop_lru(self):
        if not self._slabs:
            return None, None
        b, entry = self._slabs.popitem(last=False)
        self._host_mirror.pop(int(b), None)
        self.stats.bytes_cached -= entry[3]
        self.stats.blocks_cached = len(self._slabs)
        return b, entry

    def host_view(self, block_id: int):
        """Host ``(dims, meas, valid, nbytes)`` of a resident slab, memoized
        for device tiers (ONE download per residency, not one per access)."""
        entry = self._slabs.get(int(block_id))
        if entry is None:
            return None
        if not self.device:
            return entry
        mirror = self._host_mirror.get(int(block_id))
        if mirror is None:
            mirror = _to_host(entry, device=True)
            self._host_mirror[int(block_id)] = mirror
        return mirror


def _to_host(slab: tuple, device: bool) -> tuple:
    """Host ``(dims [R,r], meas [R,s], valid [R], nbytes)`` view of a tier
    slab.  Device slabs (lane-dense) download and transpose back to the
    row-major host layout, under an explicit transfer-guard allow so callers
    may run the surrounding loop under a ``"disallow"`` stray-transfer
    probe."""
    if not device:
        return slab
    import jax

    with jax.transfer_guard_device_to_host("allow"):
        return (
            np.ascontiguousarray(np.asarray(slab[0]).T),
            np.ascontiguousarray(np.asarray(slab[1]).T),
            np.asarray(slab[2]),
            slab[3],
        )


def _to_tier(slab: tuple, device: bool) -> tuple:
    """Convert an owned slab to a tier's residency format (upload/download)."""
    is_dev = not isinstance(slab[0], np.ndarray)
    if device and not is_dev:
        import jax.numpy as jnp

        return (jnp.asarray(np.ascontiguousarray(slab[0].T)),
                jnp.asarray(np.ascontiguousarray(slab[1].T)),
                jnp.asarray(slab[2]), slab[3])
    if not device and is_dev:
        return _to_host(slab, device=True)
    return slab


class TierStack:
    """Byte-budgeted storage tiers with cost-model-arbitrated placement.

    Parameters
    ----------
    tiers : Sequence[Tier]
        Fast-to-slow cache tiers (tier 0 first).  The backing store is the
        implicit bottom level — always consistent, never "full".
    backing : CostModel | None
        Cost model of the backing store (defaults to the paper's ``hdd``);
        prices misses in :meth:`effective_io_time` and anchors the placement
        policy's io_time-saved-per-byte scores.
    policy : PlacementPolicy | None
        The placement arbiter; defaults to
        :class:`~repro.storage.policy.CostAwarePolicy`.
    device_fill : bool | None
        Fill device tiers through ``store.gather_device`` (the Pallas union
        gather).  ``None`` auto-selects: the kernel path on TPU backends, a
        host fetch + upload elsewhere (interpret-mode gathers are correct
        but slow).  Force ``True`` to exercise the kernel fill anywhere.

    Notes
    -----
    ``stats`` aggregates the flat-LRU counters (hits/misses/evictions/
    store fetches/bytes) so every existing consumer of
    ``NeedleTailEngine.block_cache.stats`` keeps working; ``evictions``
    counts only blocks dropped *out of the stack* — a demotion is not an
    eviction.  Per-tier placement counters live on each ``Tier.stats`` and
    are exported flat by :meth:`tier_counters`.
    """

    def __init__(
        self,
        tiers: Sequence[Tier],
        backing: CostModel | None = None,
        policy: PlacementPolicy | None = None,
        device_fill: bool | None = None,
    ):
        if not tiers:
            raise ValueError("TierStack needs at least one tier")
        self.tiers = list(tiers)
        self.backing = backing or make_cost_model("hdd")
        self.policy = policy or CostAwarePolicy()
        self.device_fill = device_fill
        self.stats = CacheStats()
        # run_batch swaps in a list for exact per-batch physical-I/O logging
        self.fetch_log: list | None = None
        self._accesses: dict[int, int] = {}  # logical touches per block id
        # ids the store reported append-dirtied: their next admission books
        # as ``invalidation_rereads`` instead of ``misses`` (one-shot marks)
        self._invalidated: set[int] = set()
        # optional repro.obs.TraceRecorder: fetch outcomes + invalidation
        # events stream into it; None (the default) adds one attribute test
        self.obs = None
        # measured-cost feedback (both optional; see repro.storage.calibration
        # and repro.core.plan_ledger): the ledger supplies per-level price
        # corrections and receives predicted-vs-observed placement records;
        # the timing backend supplies observations and powers calibrate().
        self.ledger = None
        self.timing_backend = None

    # ------------------------------------------------------------------ admin
    def __contains__(self, block_id: int) -> bool:
        return self._find(int(block_id)) is not None

    def __len__(self) -> int:
        return sum(len(t) for t in self.tiers)

    @property
    def nbytes(self) -> int:
        return self.stats.bytes_cached

    def accesses(self, block_id: int) -> int:
        """Logical access count of `block_id` (policy scoring input)."""
        return self._accesses.get(int(block_id), 0)

    def access_counts(self) -> dict[int, int]:
        """Copy of the per-block logical-access ledger — the heat input
        :class:`repro.storage.rebalance.HeatTracker` samples per shard."""
        return dict(self._accesses)

    def _find(self, block_id: int) -> int | None:
        for t, tier in enumerate(self.tiers):
            if block_id in tier:
                return t
        return None

    def _sync_gauges(self) -> None:
        self.stats.bytes_cached = sum(t.stats.bytes_cached for t in self.tiers)
        self.stats.blocks_cached = sum(len(t) for t in self.tiers)

    def clear(self) -> None:
        self.stats.invalidations += len(self)
        for tier in self.tiers:
            tier.stats.invalidations += len(tier)
            tier._slabs.clear()
            tier._host_mirror.clear()
            tier.stats.bytes_cached = 0
            tier.stats.blocks_cached = 0
        self._accesses.clear()
        # wholesale swap: the next reads hit genuinely new data (cold misses)
        self._invalidated.clear()
        self._sync_gauges()

    def invalidate(self, block_ids: Iterable[int]) -> int:
        """Evict exactly `block_ids` from EVERY tier (the append-dirtied
        tail); returns the number of resident copies evicted."""
        n = 0
        marked = 0
        for b in block_ids:
            b = int(b)
            self._invalidated.add(b)
            marked += 1
            for tier in self.tiers:
                if tier.pop(b) is not None:
                    tier.stats.invalidations += 1
                    n += 1
            self._accesses.pop(b, None)
        if len(self._invalidated) > (1 << 20):  # safety valve: marks degrade
            self._invalidated.clear()  # to plain misses, never grow unbounded
        self.stats.invalidations += n
        self._sync_gauges()
        if self.obs is not None:
            self.obs.event("tier.invalidate", dirtied=marked, evicted=n)
        return n

    def _split_rereads(self, miss_set: set[int]) -> set[int]:
        """Partition a miss set: returns the append-invalidated ids in it
        (consuming their one-shot marks); the caller books those as
        ``invalidation_rereads`` and the rest as cold ``misses``."""
        if not self._invalidated:
            return set()
        re_ids = self._invalidated & miss_set
        if re_ids:
            self._invalidated -= re_ids
        return re_ids

    # ------------------------------------------------------------- residency
    def residency_tier(self, block_ids) -> np.ndarray:
        """Tier index per id; ``len(self.tiers)`` marks a miss (backing)."""
        ids = np.asarray(block_ids, dtype=np.int64).ravel()
        out = np.full(ids.shape, len(self.tiers), dtype=np.int64)
        for i, b in enumerate(ids):
            t = self._find(int(b))
            if t is not None:
                out[i] = t
        return out

    def _corr(self, level: str) -> float:
        """Plan-ledger price correction for tier/backing `level` (1.0 if none)."""
        lg = self.ledger
        return lg.correction(level) if lg is not None else 1.0

    def effective_io_time(self, block_ids, backing: CostModel | None = None) -> float:
        """Residency-aware modeled I/O time of fetching `block_ids`.

        Each tier's resident ids are priced as one §4.1 ascending pass under
        that tier's cost model; misses under `backing` (default: the stack's
        backing model).  This is the "effective tier cost" the residency-
        aware §7.2 auto arbitration compares candidate plans with.  When a
        plan ledger is attached, each component is scaled by that level's
        running q-error correction — so repeated misprediction shifts the
        price toward observed costs even between recalibrations."""
        backing = backing or self.backing
        ids = np.asarray(block_ids, dtype=np.int64).ravel()
        if ids.size == 0:
            return 0.0
        where = self.residency_tier(ids)
        total = 0.0
        for t, tier in enumerate(self.tiers):
            sel = ids[where == t]
            if sel.size:
                total += tier.cost.io_time(sel) * self._corr(tier.name)
        miss = ids[where == len(self.tiers)]
        if miss.size:
            total += backing.io_time(miss) * self._corr(backing.name)
        return total

    def calibrate(self, backend=None, **fit_kw) -> dict:
        """Refit every measurable tier/backing `CostModel` from `backend`
        timings in place (see :func:`repro.storage.calibration.
        calibrate_stack`); returns ``{level: fitted CostModel}``.  With no
        argument, reuses the backend retained by a previous calibration."""
        from repro.storage.calibration import calibrate_stack

        be = backend if backend is not None else self.timing_backend
        if be is None:
            raise ValueError("TierStack.calibrate needs a timing backend")
        return calibrate_stack(self, be, **fit_kw)

    def get_device(self, store: "BlockStore", block_ids) -> tuple:
        """Device-resident gather for device-side slab consumers (e.g.
        exemplar measures feeding an LM): serve every id from tier-0
        residency without a device→host transfer, filling misses through
        :meth:`ensure` first and uploading lower-tier residents on demand.
        Returns jax ``(dims [B,R,r], meas [B,R,s], valid [B,R])``
        byte-identical to ``store.fetch(block_ids)``.  Requires tier
        0 to be a device tier.  (The ``run_batch`` loops do NOT use this —
        they mask records on the host and go through :meth:`get_many`.)"""
        import jax.numpy as jnp

        if not self.tiers[0].device:
            raise ValueError("get_device requires a device tier at level 0")
        ids = np.asarray(block_ids, dtype=np.int64).ravel()
        if ids.size == 0:
            return store.fetch_device(ids)

        def reread(g: np.ndarray):
            """Accounted backing-store read of `g`, lane-dense device rows."""
            self.stats.store_fetch_calls += 1
            self.stats.store_blocks_fetched += int(g.size)
            if self.fetch_log is not None:
                self.fetch_log.append(g)
            return store.gather_device(g)[:3]

        pre = {int(b) for b in ids if self._find(int(b)) is not None}
        self.ensure(store, ids)
        # device gathers are logical accesses like any other: they feed the
        # policy's frequency scores (so get_device traffic earns its blocks
        # promotion and protects them from victim selection) and the ledger
        for b in ids:
            b = int(b)
            self._accesses[b] = self._accesses.get(b, 0) + 1
            t = self._find(b)
            if t is not None and b in pre:
                self.tiers[t].touch(b)
                self.tiers[t].stats.hits += 1
                self.stats.hits += 1
                self._promote_if_worthy(b, t)
        # blocks displaced out of the stack by this very ensure (total
        # budget under the request): ONE batched re-read, accounted like
        # every other backing-store fetch
        gone = sorted({int(b) for b in ids if self._find(int(b)) is None})
        gone_off: dict[int, int] = {}
        gd = gm = gv = None
        if gone:
            gd, gm, gv = reread(np.asarray(gone, dtype=np.int64))
            gone_off = {b: off for off, b in enumerate(gone)}
        out_d, out_m, out_v = [], [], []
        tier0 = self.tiers[0]
        for b in ids:
            b = int(b)
            entry = tier0.peek(b)
            if entry is None:
                if b in gone_off:
                    off = gone_off[b]
                    out_d.append(gd[off]); out_m.append(gm[off]); out_v.append(gv[off])
                    continue
                # resident lower: pull up on demand (upload, no residency move)
                t = self._find(b)
                raw = self.tiers[t].peek(b) if t is not None else None
                if raw is None and t is not None:
                    # view tiers (peer) serve copies through host_view only
                    raw = self.tiers[t].host_view(b)
                if raw is None:
                    # residency vanished mid-gather (peer died/evicted): one
                    # accounted re-read keeps the gather byte-identical
                    d1, m1, v1 = reread(np.asarray([b], dtype=np.int64))
                    out_d.append(d1[0]); out_m.append(m1[0]); out_v.append(v1[0])
                    continue
                entry = _to_tier(raw, device=True)
            out_d.append(entry[0]); out_m.append(entry[1]); out_v.append(entry[2])
        # tier-0 slabs are lane-dense; callers see fetch()'s row-major layout
        return (jnp.stack(out_d).transpose(0, 2, 1),
                jnp.stack(out_m).transpose(0, 2, 1), jnp.stack(out_v))

    # ------------------------------------------------------------- placement
    def _drop(self, tier_idx: int, block_id: int, entry: tuple) -> None:
        self.tiers[tier_idx].stats.evictions += 1
        self.stats.evictions += 1
        self._accesses.pop(int(block_id), None)

    def _resolve_target(self, tier_idx: int | None, nbytes: int) -> int | None:
        """Walk the demote chain until a tier that can hold `nbytes` at all;
        ``None`` means the slab leaves the stack."""
        while tier_idx is not None and not self.tiers[tier_idx].fits_at_all(nbytes):
            tier_idx = self.policy.demote_target(self, tier_idx)
        return tier_idx

    def _place(self, tier_idx: int, block_id: int, slab: tuple, *, how: str) -> None:
        """Insert `slab` at `tier_idx`, displacing residents per the policy
        (victim selection + demotion cascade).  A slab too large for the
        tier's whole budget falls through to the demotion target; a fresh
        admission that fits nowhere is simply not admitted (the backing
        store still holds it, and it was never resident, so nothing is
        evicted)."""
        tier_idx = self._resolve_target(tier_idx, slab[3])
        if tier_idx is None:
            self._accesses.pop(int(block_id), None)
            return
        tier = self.tiers[tier_idx]
        while not tier.has_room(slab[3]) and len(tier):
            victim = self.policy.victim(self, tier_idx)
            if victim is None or victim not in tier:
                victim, ventry = tier.pop_lru()
            else:
                ventry = tier.pop(victim)
            # resolve where the victim can actually land BEFORE writing the
            # demotion ledger: a "demotion" whose every lower tier is too
            # small for the slab is a drop, and must be counted as one
            target = self._resolve_target(
                self.policy.demote_target(self, tier_idx), ventry[3]
            )
            if target is None:
                self._drop(tier_idx, victim, ventry)
            else:
                tier.stats.demotions_out += 1
                self.tiers[target].stats.demotions_in += 1
                self._place(target, victim, _to_tier(ventry, self.tiers[target].device),
                            how="demote")
        tier.put(int(block_id), _to_tier(slab, tier.device))
        st = tier.stats
        if how == "admit":
            st.admissions += 1
        elif how == "promote":
            st.promotions_in += 1
        self._sync_gauges()

    def _promote_if_worthy(self, block_id: int, tier_idx: int) -> None:
        """Policy hook on a hit: move the block up one level if the arbiter
        says so.  Callers re-resolve residency afterwards (`_find`) — the
        promotion cascade may land the block elsewhere or even drop it."""
        target = self.policy.promote_tier(self, block_id, tier_idx)
        if target is None or target >= tier_idx:
            return
        entry = self.tiers[tier_idx].peek(block_id)
        if entry is None:  # defensive: racing policies
            return
        # one level at a time, whatever the policy says — and only if the
        # slab can actually LAND strictly above (a policy without its own
        # fits_at_all guard must not produce a pop/re-insert that the ledger
        # would record as a promotion that never happened)
        land = self._resolve_target(tier_idx - 1, entry[3])
        if land is None or land >= tier_idx:
            return
        entry = self.tiers[tier_idx].pop(block_id)
        self._place(land, block_id, entry, how="promote")

    # ------------------------------------------------------------------ fetch
    @staticmethod
    def block_nbytes(store: "BlockStore") -> int:
        """Bytes of one block slab ``(dims i32 [R,r], meas f32 [R,s],
        valid bool [R])`` of `store` — the unit tier budgets are sized in
        (benchmarks and tests derive working-set budgets from it)."""
        r = int(store.dims.shape[-1])
        s = int(store.measures.shape[-1])
        return store.records_per_block * (r * 4 + s * 4 + 1)

    def _use_device_fill(self) -> bool:
        if self.device_fill is not None:
            return bool(self.device_fill)
        import jax

        return jax.default_backend() == "tpu"

    def _fetch_and_admit(self, store: "BlockStore", miss: np.ndarray) -> dict:
        """Read `miss` (ascending) from the backing store and admit each
        block at its policy-chosen tier.  Device-tier admissions fill through
        ``store.gather_device`` (the HBM fill path) when enabled; everything
        else through one host ``store.fetch``.  Returns
        ``block_id -> (dims, meas, valid)`` for the in-scope miss batch,
        host or device arrays as fetched — the gather fallback when a budget
        smaller than the request evicts a block the same call admitted.
        Conversion to host bytes is the CALLER's, done lazily: the
        ``ensure`` path discards the dict, so an eager download of every
        device-admitted slab would be one wasted device→host transfer per
        cold block."""
        nb = self.block_nbytes(store)
        # predicted price of this miss batch BEFORE fetching (corrected by the
        # ledger like every other quote); the observation closes the loop below
        # — the trace recorder consumes the same predicted/observed pair, so
        # pricing is computed whenever EITHER consumer is wired
        priced = (self.ledger is not None or self.obs is not None) and miss.size
        pred = 0.0
        t_wall = 0.0
        if priced:
            pred = self.backing.io_time(miss) * self._corr(self.backing.name)
            t_wall = time.perf_counter()
        # sequential admission decisions: reserve bytes as targets are chosen
        # so the policy sees the tier filling up across the miss batch
        targets: dict[int, int] = {}
        try:
            for b in miss:
                t = self.policy.admit_tier(self, int(b), nb)
                targets[int(b)] = t
                self.tiers[t].reserved_bytes += nb
        finally:
            for tier in self.tiers:
                tier.reserved_bytes = 0
        dev_fill = self._use_device_fill()
        dev_ids = np.asarray(
            sorted(b for b, t in targets.items() if self.tiers[t].device and dev_fill),
            dtype=np.int64,
        )
        host_ids = np.asarray(
            sorted(set(targets) - {int(b) for b in dev_ids}), dtype=np.int64
        )
        inscope: dict[int, tuple] = {}
        calls = 0
        if host_ids.size:
            calls += 1
            if self.fetch_log is not None:
                self.fetch_log.append(host_ids)
            bd, bm, bv = store.fetch(host_ids)
            for off, b in enumerate(host_ids):
                slab = (np.array(bd[off]), np.array(bm[off]), np.array(bv[off]))
                nbytes = sum(int(a.nbytes) for a in slab)
                inscope[int(b)] = slab
                self._place(targets[int(b)], int(b), (*slab, nbytes), how="admit")
        if dev_ids.size:
            calls += 1
            if self.fetch_log is not None:
                self.fetch_log.append(dev_ids)
            dd, dm, dv, _ = store.gather_device(dev_ids)
            for off, b in enumerate(dev_ids):
                slab_dev = (dd[off], dm[off], dv[off])
                nbytes = sum(int(a.nbytes) for a in slab_dev)
                inscope[int(b)] = slab_dev
                self._place(targets[int(b)], int(b), (*slab_dev, nbytes), how="admit")
        self.stats.store_fetch_calls += calls
        self.stats.store_blocks_fetched += int(miss.size)
        if priced:
            from repro.storage.calibration import measurable

            be = self.timing_backend
            # a backend wrapping THIS store would re-fetch to answer — the
            # demand fetch we just timed is already the observation there
            if be is not None and measurable(be, self.backing.name) and \
                    getattr(be, "store", None) is not store:
                obs = be.io_seconds(self.backing.name, miss)
            else:
                obs = time.perf_counter() - t_wall
            if self.ledger is not None:
                self.ledger.record("placement", self.backing.name, pred, obs)
            if self.obs is not None:
                self.obs.event(
                    "fetch.store", n=int(miss.size), level=self.backing.name,
                    predicted_io_s=pred, observed_io_s=obs,
                )
        return inscope

    def ensure(self, store: "BlockStore", block_ids) -> int:
        """Admit every miss among `block_ids` (ascending §4.1 order); returns
        the number of blocks physically read from the backing store."""
        ids = np.asarray(block_ids, dtype=np.int64).ravel()
        miss_set = {int(b) for b in ids if self._find(int(b)) is None}
        if not miss_set:
            return 0
        miss = np.asarray(sorted(miss_set), dtype=np.int64)
        re_ids = self._split_rereads(miss_set)
        # admissions are logical misses — except append-invalidated re-reads
        self.stats.misses += int(miss.size) - len(re_ids)
        self.stats.invalidation_rereads += len(re_ids)
        self._fetch_and_admit(store, miss)
        return int(miss.size)

    def prefetch(self, store: "BlockStore", block_ids, tier: int = 0,
                 slabs: dict | None = None) -> int:
        """Speculatively promote `block_ids` into `tier` ahead of demand
        (the serving loop's next-wave warm-up: ``repro.storage.prefetch``).

        Blocks already resident at or above `tier` are untouched; residents
        below it are promoted (``promotions_in`` on the landing tier);
        misses are read from the backing store — or taken from `slabs`
        (``block_id -> (dims, meas, valid)`` host arrays, the async
        prefetch thread's completed reads) without touching the store — and
        admitted at `tier` (normal victim/demotion cascade applies, so a
        too-hot prefetch can never wedge the tier).  Speculative by design:
        **no hit/miss accounting** — demand counters stay meaningful, only
        ``store_fetch_calls`` / ``store_blocks_fetched`` and the
        ``fetch_log`` record the physical reads.  Returns how many blocks
        are resident anywhere in the stack afterwards (a slab the budget
        immediately re-evicted does not count).
        """
        if not (0 <= tier < len(self.tiers)):
            raise ValueError(f"tier {tier} out of range")
        ids = np.asarray(block_ids, dtype=np.int64).ravel()
        todo: list[int] = []
        seen: set[int] = set()
        for b in ids:
            b = int(b)
            if b not in seen:
                seen.add(b)
                todo.append(b)
        miss: list[int] = []
        for b in todo:
            at = self._find(b)
            if at is None:
                miss.append(b)
            elif at > tier:
                entry = self.tiers[at].pop(b)
                # a view tier (repro.storage.peer.PeerTier) owns no slab to
                # move: the block stays remote and still counts as resident
                if entry is not None:
                    self._place(tier, b, entry, how="promote")
        if miss:
            have = {b: slabs[b] for b in miss if slabs and b in slabs}
            need = np.asarray(sorted(set(miss) - set(have)), dtype=np.int64)
            if need.size:
                if self.fetch_log is not None:
                    self.fetch_log.append(need)
                bd, bm, bv = store.fetch(need)  # ascending §4.1 order
                self.stats.store_fetch_calls += 1
                self.stats.store_blocks_fetched += int(need.size)
                for off, b in enumerate(need):
                    have[int(b)] = (
                        np.array(bd[off]), np.array(bm[off]), np.array(bv[off])
                    )
            for b in sorted(have):
                slab = have[b]
                nbytes = sum(int(np.asarray(a).nbytes) for a in slab)
                self._place(tier, int(b), (*slab, nbytes), how="admit")
        return sum(1 for b in todo if self._find(b) is not None)

    def get_many(
        self, store: "BlockStore", block_ids
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Gather host slabs for `block_ids` (order preserved), fetching every
        miss from the backing store in one ascending pass per fill path.

        Returns ``(dims [B,R,r], measures [B,R,s], valid [B,R])`` —
        byte-identical to ``store.fetch(block_ids)`` under any budgets and
        any placement policy."""
        ids = np.asarray(block_ids, dtype=np.int64).ravel()
        if ids.size == 0:
            return store.fetch(ids)
        miss_set = {int(b) for b in ids if self._find(int(b)) is None}
        hits = sum(1 for b in ids if int(b) not in miss_set)
        self.stats.hits += int(hits)
        re_ids = self._split_rereads(miss_set)
        n_re = sum(1 for b in ids if int(b) in re_ids) if re_ids else 0
        self.stats.misses += int(ids.size - hits) - n_re
        self.stats.invalidation_rereads += n_re
        inscope: dict[int, tuple] = {}
        if miss_set:
            miss = np.asarray(sorted(miss_set), dtype=np.int64)
            inscope = self._fetch_and_admit(store, miss)

        out_d, out_m, out_v = [], [], []
        for b in ids:
            b = int(b)
            self._accesses[b] = self._accesses.get(b, 0) + 1
            t = self._find(b)
            if t is not None:
                tier = self.tiers[t]
                tier.touch(b)
                if b not in miss_set:
                    tier.stats.hits += 1
                    self._promote_if_worthy(b, t)
                host = None
                t2 = self._find(b)  # promotion may have moved (or dropped) it
                if t2 is not None:
                    host = self.tiers[t2].host_view(b)
                if host is not None:
                    out_d.append(host[0]); out_m.append(host[1]); out_v.append(host[2])
                    continue
            if b in inscope:
                # admitted this call but already displaced out of the stack
                # (budgets smaller than the request): serve the in-scope
                # copy, downloading device-fetched slabs only here
                slab = inscope[b]
                if not isinstance(slab[0], np.ndarray):
                    slab = _to_host((*slab, 0), device=True)[:3]
                out_d.append(slab[0]); out_m.append(slab[1]); out_v.append(slab[2])
            else:
                # a pre-call hit evicted by this call's own placements: the
                # one case left needing a re-read
                one = np.asarray([b], dtype=np.int64)
                self.stats.store_fetch_calls += 1
                self.stats.store_blocks_fetched += 1
                if self.fetch_log is not None:
                    self.fetch_log.append(one)
                bd1, bm1, bv1 = store.fetch(one)
                out_d.append(bd1[0]); out_m.append(bm1[0]); out_v.append(bv1[0])
        if self.ledger is not None and self.timing_backend is not None:
            self._record_hit_observations(ids, miss_set)
        return np.stack(out_d), np.stack(out_m), np.stack(out_v)

    def _record_hit_observations(self, ids: np.ndarray, miss_set: set[int]) -> None:
        """Close the pricing loop for resident hits: record each tier's quoted
        vs backend-observed io_time for the ids this gather served from it.
        Only meaningful with a timing backend (wall-clocking a cache hit is
        noise); levels the backend cannot measure are skipped."""
        from repro.storage.calibration import measurable

        lg, be = self.ledger, self.timing_backend
        res = np.unique(np.asarray(
            [int(b) for b in ids if int(b) not in miss_set], dtype=np.int64))
        if res.size == 0:
            return
        where = self.residency_tier(res)
        for t, tier in enumerate(self.tiers):
            sel = res[where == t]
            if sel.size and measurable(be, tier.name):
                pred = tier.cost.io_time(sel) * self._corr(tier.name)
                lg.record("placement", tier.name, pred, be.io_seconds(tier.name, sel))

    # ------------------------------------------------------------- reporting
    def tier_counters(self) -> dict[str, int]:
        """Flat monotonic per-tier counters, keyed ``"<tier>.<counter>"``
        (``hbm.hits``, ``dram.demotions_in``, ...) — the per-wave placement
        ledger ``run_batch`` diffs into ``BatchQueryResult.tier_stats``."""
        out: dict[str, int] = {}
        for tier in self.tiers:
            s = tier.stats
            for k in ("hits", "admissions", "promotions_in", "demotions_in",
                      "demotions_out", "evictions", "invalidations"):
                out[f"{tier.name}.{k}"] = getattr(s, k)
            extra = getattr(tier, "extra_counters", None)
            if extra is not None:  # e.g. peer.remote_fetches / peer.migrations
                for k, v in extra().items():
                    out[f"{tier.name}.{k}"] = int(v)
        return out

    def snapshot(self) -> dict:
        """Aggregate + per-tier stats (gauges included), for logging."""
        return {
            "aggregate": self.stats.snapshot(),
            "tiers": {t.name: t.stats.snapshot() for t in self.tiers},
        }


def make_tier_stack(
    hbm_bytes: int | None,
    dram_bytes: int | None = None,
    backing: CostModel | str = "hdd",
    block_bytes: int = 256 * 1024,
    policy: PlacementPolicy | None = None,
    device_fill: bool | None = None,
) -> TierStack:
    """The canonical two-tier stack: HBM device buffers over host DRAM.

    Parameters
    ----------
    hbm_bytes, dram_bytes : int | None
        Byte budgets (``None`` = unbounded) for the device and host tiers.
    backing : CostModel | str
        Backing-store cost model (or a ``make_cost_model`` preset name).
    block_bytes : int
        Block size fed to the ``hbm`` / ``dram`` preset constructors.
    policy, device_fill
        Forwarded to :class:`TierStack`.
    """
    if isinstance(backing, str):
        backing = make_cost_model(backing, block_bytes)
    return TierStack(
        tiers=[
            Tier("hbm", hbm_bytes, make_cost_model("hbm", block_bytes), device=True),
            Tier("dram", dram_bytes, make_cost_model("dram", block_bytes)),
        ],
        backing=backing,
        policy=policy,
        device_fill=device_fill,
    )
