"""Wave-batched serving engine over prefill + decode_step.

Requests are drained from the admission queue in waves of ``max_slots``:
each wave's prompts are left-padded to a common length (BOS padding), prefilled
as one batch, then decoded in lock-step — one jitted decode_step per tick for
the whole wave.  Batch rows are independent, so finished rows simply stop
sampling (their KV writes are self-consistent garbage that cannot leak across
rows).  This is the static/wave variant of continuous batching: the scheduling
layer is real (queue, waves, per-request lengths/EOS), while the position
counter stays scalar — the shape the multi-pod decode dry-run lowers.

The NeedleTail tie-in: :meth:`select_exemplars` retrieves k cached exemplars
matching request predicates through the any-k engine (few-shot selection
without scanning the exemplar store).  Exemplar lookups are admitted through
an SLO admission controller (:mod:`repro.serving.admission`): requests
accumulate under a configurable latency SLO / max-wave-size policy and waves
launch opportunistically — :meth:`pump_exemplar_requests` runs only the waves
that are ready (full, or oldest deadline due), :meth:`drain_exemplar_requests`
is the flush-everything barrier.  Each launched wave goes through ONE batched
any-k call (:meth:`NeedleTailEngine.any_k_batch`), so concurrent requests
share one vectorized plan, the engine-lifetime block LRU, and the cross-batch
plan-order memo instead of Q independent engine passes.  When a device mesh is
configured (``exemplar_mesh=...``, or the any-k engine already has one
attached), each wave's plan additionally runs as ONE ``shard_map`` collective
over the λ-sharded density maps (:mod:`repro.core.sharded`) — the whole wave
is planned by a single collective instead of per-shard host mirrors.

With ``exemplar_device=True`` the wave runs the **device-resident pipeline**
(:mod:`repro.core.multi_query` ``plan_on_host=False``): the plan state stays
on device across refill rounds and :meth:`pump_exemplar_requests` consumes
exactly ONE packed device→host transfer per round, while the wave's fetch
set is filtered through real :class:`~repro.core.block_cache.BlockLRUCache`
residency — a wave whose needs are covered by cache residency alone performs
0 store reads and 0 store gathers (``last_wave_stats`` reports the per-wave
transfer/residency accounting).

**Continuous batching** (:meth:`ServeEngine.step` / :meth:`run_continuous`)
replaces the drain-the-wave loops above with one slot-level loop: a
:class:`SlotScheduler` owns a fixed pool of ``max_slots`` slots, requests
join between refill rounds and leave the instant their k rows (or EOS) are
satisfied, and freed slots are refilled from the admission queue *mid-wave*
(``AdmissionController.claim``) — for both exemplar any-k requests and LM
decode requests, behind the same ``step()`` tick.  A finished query never
holds its slot while stragglers refill, which is where the p99/SLO win over
``run_until_drained`` comes from under sustained traffic
(``benchmarks/bench_multi_query.py --serving``).  Per-request results stay
byte-identical to solo runs — rows of a wave are planned independently
(:class:`repro.core.multi_query.DeviceWave`), so batching changes the I/O
schedule, never the bytes.  With ``exemplar_prefetch=True`` the loop also
warms the *predicted next wave* (``repro.storage.prefetch.TierPrefetcher``)
into tier 0 while the current round plans, and
``AdmissionPolicy.cheap_cost_s`` arms the cost-fed launch gate
(``repro.storage.prefetch.make_missed_cost_probe``).
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import decode as D
from repro.obs import span
from repro.serving.admission import AdmissionController, AdmissionPolicy


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # [P] int32
    max_new_tokens: int = 32
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class ExemplarRequest:
    """Queued few-shot exemplar lookup: k records matching the predicates."""

    rid: int
    predicates: Any
    k: int
    op: str = "and"
    result: Any = None  # QueryResult once the wave it rode in has run
    done: bool = False


@dataclasses.dataclass
class AggregateRequest:
    """Queued online aggregate (the BlinkDB contract): mean/total of
    ``measure`` over the predicates, answered the moment its 95% CI
    half-width closes under ``error_slo`` OR its modeled-I/O ``deadline_s``
    budget would be overrun by the next chunk — whichever SLO the caller
    set.  With neither, the request runs to ``max_rounds`` / design
    exhaustion (best exact-ish answer)."""

    rid: int
    predicates: Any
    measure: int
    k: int  # design-split seed (chosen-arm size), not a row target
    op: str = "and"
    error_slo: float | None = None  # target CI half-width on the mean
    deadline_s: float | None = None  # modeled demand-I/O budget
    alpha: float = 0.3
    estimator: str = "ratio"
    algo: str = "threshold"
    seed: int = 0
    chunk_blocks: int = 8
    max_rounds: int = 64
    result: Any = None  # final Estimate once answered
    stream: list = dataclasses.field(default_factory=list)  # per-round Estimates
    reason: str | None = None  # "ci" | "deadline" | "exhausted" | "budget"
    rounds: int = 0
    spent_io_s: float = 0.0
    done: bool = False


def _merge_lm_cache_rows(cache, joined, row_mask: np.ndarray):
    """Graft joiner batch rows from `joined` (a freshly prefilled cache)
    into the live decode cache.  Every decode-cache leaf is laid out
    ``[n_layers, batch, ...]`` (:func:`repro.models.decode.init_cache` —
    conv/ssd/k/v alike), so one ``[batch]`` mask broadcast at axis 1 splices
    per-slot state; incumbent rows pass through untouched (batch rows are
    independent, nothing can leak across)."""
    mask = jnp.asarray(np.asarray(row_mask, bool))

    def merge(a, b):
        m = mask.reshape((1, mask.shape[0]) + (1,) * (a.ndim - 2))
        return jnp.where(m, b, a)

    return jax.tree.map(merge, cache, joined)


class SlotScheduler:
    """A fixed pool of serving slots with join/leave bookkeeping.

    The continuous loop's occupancy ledger: every round ticks
    ``busy_slot_rounds`` by the number of occupied slots, so
    :attr:`occupancy` is the busy-slot fraction per round — the steady-state
    health metric the serving smoke asserts ≥ 0.9.  Slot items are opaque
    (the exemplar loop stores ``(request, refill_state)`` pairs).
    """

    def __init__(self, n_slots: int):
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        self.n_slots = n_slots
        self.slots: list[Any] = [None] * n_slots
        self.joins = 0
        self.leaves = 0
        self.rounds = 0
        self.busy_slot_rounds = 0

    @property
    def busy(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    def free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def busy_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s is not None]

    def join(self, item: Any) -> int:
        """Seat `item` in the lowest free slot; returns the slot index."""
        for i, s in enumerate(self.slots):
            if s is None:
                self.slots[i] = item
                self.joins += 1
                return i
        raise ValueError("no free slot")

    def leave(self, slot: int) -> Any:
        item = self.slots[slot]
        if item is None:
            raise ValueError(f"slot {slot} is already free")
        self.slots[slot] = None
        self.leaves += 1
        return item

    def tick(self) -> None:
        """Account one executed round at the current occupancy."""
        self.rounds += 1
        self.busy_slot_rounds += self.busy

    @property
    def occupancy(self) -> float:
        """Busy-slot fraction per executed round, pool lifetime."""
        if self.rounds == 0:
            return 0.0
        return self.busy_slot_rounds / (self.rounds * self.n_slots)


class _ExemplarLoop:
    """Mutable state of the continuous exemplar loop: the slot pool, the
    (optional) device-resident wave, and the loop-lifetime first-touch
    ledger.  Rebuilt whenever the serving engine is pointed at a different
    any-k engine; the device wave alone is rebuilt when the engine's store
    is swapped (append) — occupants re-join with their refill state
    intact."""

    def __init__(self, engine, n_slots: int, device: bool):
        self.engine = engine
        self.sched = SlotScheduler(n_slots)
        self.device = device
        self.store = engine.store
        self.dwave = None
        if device:
            self._build_dwave()
        self.touched: list[int] = []
        self.touched_set: set[int] = set()

    def _build_dwave(self) -> None:
        from repro.core.multi_query import DeviceWave

        self.dwave = DeviceWave(
            self.engine,
            self.sched.n_slots,
            default_algo="auto",
            planner=getattr(self.engine, "distributed", None),
        )
        self.store = self.engine.store

    def sync_store(self) -> None:
        """Store swapped under us (append grew it): rebuild the device wave
        against the new λ and re-seat the occupants — their exclusion sets
        and needs carry over, the device combined rows recompute against the
        fresh densities on the next round's join flush."""
        if self.engine.store is self.store:
            return
        if self.device:
            old = self.dwave
            self._build_dwave()
            for slot in self.sched.busy_slots():
                _, st = self.sched.slots[slot]
                self.dwave.join(slot, st)
            del old
        else:
            self.store = self.engine.store
        # block ids are stable under append, but invalidated blocks will be
        # re-read on demand; the first-touch ledger stays (accounting only)


class _AggregateLoop:
    """Mutable state of the continuous online-aggregation loop: one slot
    pool whose items are ``(AggregateRequest, OnlineAggregator)`` pairs.
    Rebuilt when the serving engine is pointed at a different any-k engine
    (stranded aggregators finalize with what they have)."""

    def __init__(self, engine, n_slots: int):
        self.engine = engine
        self.sched = SlotScheduler(n_slots)


class ServeEngine:
    def __init__(
        self,
        cfg: ArchConfig | None,
        params: Any,
        max_slots: int = 4,
        max_seq: int = 256,
        eos_id: int | None = None,
        pad_id: int = 0,
        rules=None,
        exemplar_policy: AdmissionPolicy | None = None,
        clock=time.monotonic,
        exemplar_mesh=None,
        exemplar_device: bool = False,
        exemplar_residency: bool = False,
        exemplar_prefetch: bool = False,
        aggregate_policy: AdmissionPolicy | None = None,
        recalibrate_every: int = 0,
        obs=None,
    ):
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.rules = rules
        # when set, exemplar waves plan through the sharded batched path:
        # the any-k engine gets this mesh attached on first wave (one
        # shard_map collective per plan wave, repro.core.sharded)
        self.exemplar_mesh = exemplar_mesh
        # when set, exemplar waves run the device-resident pipeline: plan
        # state carried on device, ONE packed device→host transfer per
        # refill round (repro.core.multi_query, plan_on_host=False)
        self.exemplar_device = exemplar_device
        # when set, pump_exemplar_requests installs a residency probe on the
        # admission controller (repro.storage.residency.wave_is_resident):
        # a wave whose every query has a memoized plan over cache-resident
        # blocks launches BEFORE its SLO deadline — it reads nothing from
        # the backing store, so waiting buys no shared-fetch savings.
        # Caveat: the probe peeks the host/sharded plan memos; device waves
        # (exemplar_device=True) never write any memo, so with that combo
        # residency launches never fire and waves use full/deadline policy
        # (see repro.storage.residency's module docstring).
        self.exemplar_residency = exemplar_residency
        # when set, the continuous loop runs a TierPrefetcher
        # (repro.storage.prefetch): each tick predicts the pending requests'
        # round-0 block union from the plan memo and promotes it into tier 0
        # while the current round is still planning, so the predicted wave's
        # first fetch is a pure tier hit
        self.exemplar_prefetch = exemplar_prefetch
        # when > 0, the continuous loop refits the any-k engine's cost
        # models from its timing backend every N exemplar ticks
        # (engine.recalibrate() — the "periodically thereafter" half of the
        # calibration pass; engine start is NeedleTailEngine(
        # calibrated_cost=True)).  No-op for engines without a backend.
        self.recalibrate_every = int(recalibrate_every)
        self._ticks_since_cal = 0
        # per-wave accounting of the most recent exemplar wave (transfer
        # ledger + BlockLRUCache residency feed); see pump_exemplar_requests
        self.last_wave_stats: dict | None = None
        # obs: a repro.obs.TraceRecorder shared by every subsystem this loop
        # drives — the admission controllers get it here, the any-k engine
        # (and its tier stack / peer group) on first tick (_wire_obs).  The
        # default None keeps every traced site at one attribute test.
        self.obs = obs
        self.queue: deque[Request] = deque()
        self.exemplar_queue: deque[ExemplarRequest] = deque()  # legacy intake
        self.exemplar_admission = AdmissionController(
            exemplar_policy or AdmissionPolicy(max_wave=max_slots), clock=clock,
            obs=obs,
        )
        self.aggregate_admission = AdmissionController(
            aggregate_policy or AdmissionPolicy(max_wave=max_slots), clock=clock,
            obs=obs,
        )
        # optional marginal-value cutoff for the answer-now arbitration
        # (modeled seconds per unit of expected CI-width reduction); None
        # keeps only the request's own error/deadline SLOs in play
        self.aggregate_max_s_per_width: float | None = None
        self._rid = itertools.count()
        self._exemplar_loop: _ExemplarLoop | None = None
        self._aggregate_loop: _AggregateLoop | None = None
        self._prefetcher = None  # (engine, TierPrefetcher) cache
        self._lm: dict | None = None  # continuous LM wave: cache/pos/slots
        if cfg is None:
            # exemplar-only serving (no LM): step()/run_continuous drive the
            # any-k slot loop first-class, the LM tick is a no-op
            self._decode = self._prefill = None
        else:
            self._decode = jax.jit(
                lambda p, c, t, pos: D.decode_step(p, c, t, pos, cfg, rules)
            )
            self._prefill = jax.jit(
                lambda p, toks: D.prefill(p, toks, cfg, rules, max_seq=max_seq)
            )

    def submit(self, prompt, max_new_tokens: int = 32) -> Request:
        req = Request(next(self._rid), np.asarray(prompt, np.int32), max_new_tokens)
        self.queue.append(req)
        obs = getattr(self, "obs", None)
        if obs is not None:
            obs.event("request.submit", rid=req.rid, kind="lm")
            obs.metrics.inc("serve.lm.submitted")
        return req

    def _next_wave(self) -> list[Request]:
        wave = []
        while self.queue and len(wave) < self.max_slots:
            wave.append(self.queue.popleft())
        return wave

    def _run_wave(self, wave: list[Request]) -> None:
        n = len(wave)
        plen = max(len(r.prompt) for r in wave)
        toks = np.full((self.max_slots, plen), self.pad_id, np.int32)
        for b, r in enumerate(wave):  # left-pad to align last prompt token
            toks[b, plen - len(r.prompt):] = r.prompt
        last, cache = self._prefill(self.params, jnp.asarray(toks))
        nxt = np.asarray(jnp.argmax(last, axis=-1))
        for b, r in enumerate(wave):
            r.out_tokens.append(int(nxt[b]))
        pos = plen
        active = set(range(n))
        while active and pos < self.max_seq - 1:
            cur = np.full(self.max_slots, self.pad_id, np.int32)
            for b in active:
                cur[b] = wave[b].out_tokens[-1]
            logits, cache = self._decode(
                self.params, cache, jnp.asarray(cur), jnp.int32(pos)
            )
            nxt = np.asarray(jnp.argmax(logits, axis=-1))
            pos += 1
            for b in list(active):
                r = wave[b]
                tok = int(nxt[b])
                r.out_tokens.append(tok)
                if (self.eos_id is not None and tok == self.eos_id) or len(
                    r.out_tokens
                ) >= r.max_new_tokens:
                    r.done = True
                    active.discard(b)
        for r in wave:
            r.done = True

    def run_until_drained(self) -> list[Request]:
        done = []
        while self.queue:
            wave = self._next_wave()
            self._run_wave(wave)
            done.extend(wave)
        return done

    # ------------------------------------------------ NeedleTail integration
    @staticmethod
    def select_exemplars(engine, predicates, k: int):
        """any-k retrieval of k cached exemplars matching request predicates."""
        return engine.any_k(predicates, k=k, algo="auto")

    def _exemplar_admission(self) -> AdmissionController:
        """The admission controller, created lazily for engines built without
        ``__init__`` (test shims); anything pushed straight onto the legacy
        ``exemplar_queue`` deque is migrated into the controller FIFO."""
        adm = getattr(self, "exemplar_admission", None)
        if adm is None:
            adm = AdmissionController(AdmissionPolicy(max_wave=self.max_slots),
                                      obs=getattr(self, "obs", None))
            self.exemplar_admission = adm
        q = getattr(self, "exemplar_queue", None)
        while q:
            adm.submit(q.popleft())
        return adm

    def _wire_obs(self, engine) -> None:
        """Share this loop's recorder with the any-k engine and everything
        under it (tier stack, peer group) so one request's trace carries the
        whole lifecycle: queue wait → admission → plan → fetch → transfer →
        satisfy.  Never overrides a recorder the engine already owns."""
        obs = getattr(self, "obs", None)
        if obs is None:
            return
        if getattr(engine, "obs", None) is None:
            engine.obs = obs
        bc = getattr(engine, "block_cache", None)
        if bc is not None and getattr(bc, "obs", "absent") is None:
            bc.obs = obs
        peer_tier = getattr(bc, "peer_tier", None)
        group = getattr(peer_tier, "group", None)
        if group is not None and getattr(group, "obs", "absent") is None:
            group.obs = obs

    def _note_wave_stats(self) -> None:
        """Mirror the wave ledger just written to ``last_wave_stats`` into
        the recorder's metrics registry (``wave.<kind>.*``).  No-op without
        a recorder — the dict ledger itself is always schema-complete."""
        obs = getattr(self, "obs", None)
        if obs is not None and self.last_wave_stats is not None:
            from repro.obs.wave_stats import record_wave_metrics

            record_wave_metrics(obs.metrics, self.last_wave_stats)

    def _install_admission_probes(self, engine, adm: AdmissionController) -> None:
        """Wire the engine-bound launch probes onto the controller: the
        residency probe (``exemplar_residency``) and the cost probe (armed
        by ``AdmissionPolicy.cheap_cost_s``).  Probes memoize template row
        bytes, so ONE probe per engine is cached across ticks; pointing the
        serving engine at a different any-k engine rebuilds them."""
        if getattr(self, "exemplar_residency", False):
            # one probe per engine, kept across ticks: the probe memoizes
            # template row bytes, and it must peek THIS engine's memo/tiers
            cached = getattr(self, "_residency_probe", None)
            if cached is None or cached[0] is not engine:
                from repro.storage.residency import make_residency_probe

                cached = (engine, make_residency_probe(engine))
                self._residency_probe = cached
            adm.residency_probe = cached[1]
        elif getattr(self, "_residency_probe", None) is not None:
            # flag flipped off: uninstall, so polls stop paying the peek and
            # resident launches stop firing
            self._residency_probe = None
            adm.residency_probe = None
        if adm.policy.cheap_cost_s is not None:
            cached = getattr(self, "_cost_probe", None)
            if cached is None or cached[0] is not engine:
                from repro.storage.prefetch import make_missed_cost_probe

                cached = (engine, make_missed_cost_probe(engine))
                self._cost_probe = cached
            adm.cost_probe = cached[1]
        elif getattr(self, "_cost_probe", None) is not None:
            self._cost_probe = None
            adm.cost_probe = None

    def _tier_prefetcher(self, engine):
        """The loop's :class:`~repro.storage.prefetch.TierPrefetcher`, one
        per engine (it registers a store invalidation listener and owns the
        speculative-hit ledger); ``None`` unless ``exemplar_prefetch``."""
        if not getattr(self, "exemplar_prefetch", False):
            return None
        cached = getattr(self, "_prefetcher", None)
        if cached is None or cached[0] is not engine:
            from repro.storage.prefetch import TierPrefetcher

            cached = (engine, TierPrefetcher(engine))
            self._prefetcher = cached
        return cached[1]

    def submit_exemplar_request(self, predicates, k: int, op: str = "and") -> ExemplarRequest:
        """Admit an exemplar lookup under the SLO policy; it rides in the next
        wave that launches (full wave, SLO deadline, or drain barrier)."""
        req = ExemplarRequest(next(self._rid), predicates, k, op)
        obs = getattr(self, "obs", None)
        if obs is not None:
            obs.event("request.submit", rid=req.rid, kind="exemplar", k=k)
            obs.metrics.inc("serve.exemplar.submitted")
        self._exemplar_admission().submit(req)
        return req

    def _run_exemplar_wave(self, engine, wave: list[ExemplarRequest]) -> None:
        from repro.core.multi_query import BatchQuery

        # mesh-configured serving: attach once, then every wave's plan runs
        # as one shard_map collective (engine.any_k_batch auto-routes)
        mesh = getattr(self, "exemplar_mesh", None)
        if mesh is not None and getattr(engine, "distributed", None) is None:
            engine.attach_mesh(mesh)
        try:
            # only pass device= when set: engine shims in tests (and older
            # engines) may not accept the kwarg on the default host path
            kwargs = {"device": True} if getattr(self, "exemplar_device", False) else {}
            batch = engine.any_k_batch(
                [BatchQuery(r.predicates, r.k, r.op) for r in wave],
                algo="auto",
                **kwargs,
            )
        except Exception:
            # put the wave back so no admitted request is silently lost
            self._exemplar_admission().requeue_front(wave)
            raise
        # the wave's fetch set was filtered through real BlockLRUCache
        # residency (cache.ensure reads only non-resident blocks); surface
        # that plus the device-transfer ledger for the serving loop.
        # "tiers" is the per-tier placement delta of THIS wave (hits /
        # promotions / demotions / evictions per tier, flat-keyed
        # "<tier>.<counter>") when the engine runs a repro.storage.TierStack,
        # None on a flat LRU — benchmarks and tests assert placement
        # behavior with it, not just totals.
        # slot_occupancy: busy-slot fraction per refill round of this wave —
        # under wave drain a satisfied query still holds its slot, so this is
        # the number the continuous loop (step()) exists to push toward 1.0
        apr = getattr(batch, "active_per_round", None) or []
        occ = (
            sum(apr) / (len(apr) * max(self.max_slots, 1)) if apr else 0.0
        )
        from repro.obs.wave_stats import make_wave_stats

        self.last_wave_stats = make_wave_stats(
            "exemplar",
            wave_size=len(wave),
            rounds=batch.rounds,
            device_transfers=batch.device_transfers,
            store_blocks_fetched=batch.store_blocks_fetched,
            cache_hits=batch.cache_hits,
            unique_blocks=int(batch.unique_blocks_fetched.size),
            tiers=batch.tier_stats,
            slot_occupancy=min(occ, 1.0),
            modeled_store_io_s=batch.modeled_store_io_s,
            pending=self._exemplar_admission().pending,
        )
        self._note_wave_stats()
        obs = getattr(self, "obs", None)
        for req, res in zip(wave, batch.results):
            req.result = res
            req.done = True
            if obs is not None:
                obs.event("request.done", rid=req.rid, kind="exemplar",
                          rounds=res.plan_rounds, records=res.num_records)

    def pump_exemplar_requests(self, engine, now: float | None = None) -> list[ExemplarRequest]:
        """Opportunistic admission tick: launch every wave that is ready
        under the SLO policy (full wave or oldest-deadline due) and evaluate
        each through one batched any-k call.  Under-filled waves whose SLO
        still has slack keep accumulating — call again later (or use
        ``exemplar_admission.next_deadline()`` to schedule the next tick).

        With ``exemplar_device=True`` each launched wave runs the
        device-resident pipeline: this tick consumes exactly one packed
        device→host transfer per refill round, and the wave's fetch set is
        fed through real block-LRU residency — a fully cache-resident wave
        completes with 0 store reads and 0 store gathers.
        With ``exemplar_residency=True`` the controller additionally
        launches a wave *early* — before its SLO deadline — when every
        pending request's plan is memoized over cache-resident blocks
        (``repro.storage.residency.wave_is_resident``: the wave would read
        nothing from the backing store, so accumulating buys nothing).

        ``self.last_wave_stats`` carries the most recent wave's
        transfer/residency ledger.  Returns the requests completed by this
        tick."""
        adm = self._exemplar_admission()
        self._install_admission_probes(engine, adm)
        done: list[ExemplarRequest] = []
        while True:
            # one wave at a time: if a wave's engine call fails, the waves
            # not yet popped stay safely queued in the controller
            wave = adm.poll(now)
            if not wave:
                return done
            self._run_exemplar_wave(engine, wave)
            done.extend(wave)

    def drain_exemplar_requests(self, engine) -> list[ExemplarRequest]:
        """Flush barrier: launch everything pending, deadlines or not, in
        FIFO waves of the policy's ``max_wave``, each wave evaluated through
        ONE batched any-k call (shared-fetch scheduling + engine-lifetime
        block LRU, :mod:`repro.core.multi_query`)."""
        adm = self._exemplar_admission()
        done: list[ExemplarRequest] = []
        while True:
            wave = adm.flush_one()  # one wave at a time: see pump
            if not wave:
                return done
            self._run_exemplar_wave(engine, wave)
            done.extend(wave)

    # ------------------------------------------------- continuous batching
    def exemplar_tick(
        self, engine, now: float | None = None, drain: bool = False
    ) -> list[ExemplarRequest]:
        """One round of the continuous exemplar loop.

        The slot-level replacement for :meth:`pump_exemplar_requests`'s
        drain-the-wave: freed slots are refilled from the admission queue
        **mid-wave** (``AdmissionController.claim(mid_wave=True)`` — a round
        is already running, freed slots are pure capacity), joiners enter
        the device-resident wave between rounds via one batched scatter
        (:class:`repro.core.multi_query.DeviceWave`), exactly ONE refill
        round executes, and every slot whose k rows are satisfied leaves
        immediately with its :class:`~repro.core.engine.QueryResult`.  An
        idle pool claims under the normal launch policy
        (full/deadline/cheap/resident), so small waves still accumulate;
        ``drain=True`` makes an idle claim unconditional (flush barrier
        semantics for :meth:`run_continuous`).

        Byte-identity: slot rows plan independently, so each request's
        refill trajectory — and therefore its rows — is identical to a solo
        ``any_k`` run against the same store/cost state; batching moves I/O,
        never bytes.  ``last_wave_stats`` carries this round's ledger
        (``slot_occupancy``, transfer count, tier deltas,
        ``modeled_store_io_s`` of demand reads, prefetch stats).  Returns
        the requests completed this tick.
        """
        self._wire_obs(engine)
        obs = getattr(self, "obs", None)
        if obs is None:
            return self._exemplar_tick_body(engine, now, drain)
        with obs.span("serve.exemplar_tick") as sp:
            done = self._exemplar_tick_body(engine, now, drain)
            sp.set(completed=len(done))
            for req in done:
                r = req.result
                obs.event("request.done", rid=req.rid, kind="exemplar",
                          rounds=getattr(r, "plan_rounds", 0),
                          records=getattr(r, "num_records", 0))
        return done

    def _exemplar_tick_body(
        self, engine, now: float | None, drain: bool
    ) -> list[ExemplarRequest]:
        from repro.core.multi_query import (
            BatchQuery, _execute_wave, finalize_query_result, new_query_state,
            plan_round_host,
        )

        adm = self._exemplar_admission()
        self._install_admission_probes(engine, adm)
        every = getattr(self, "recalibrate_every", 0)
        if every and hasattr(engine, "recalibrate"):
            self._ticks_since_cal = getattr(self, "_ticks_since_cal", 0) + 1
            if self._ticks_since_cal >= every:
                engine.recalibrate()
                self._ticks_since_cal = 0
        mesh = getattr(self, "exemplar_mesh", None)
        if mesh is not None and getattr(engine, "distributed", None) is None:
            engine.attach_mesh(mesh)
        loop = self._exemplar_loop
        if (
            loop is None
            or loop.engine is not engine
            or loop.sched.n_slots != self.max_slots
            or loop.device != bool(getattr(self, "exemplar_device", False))
        ):
            loop = _ExemplarLoop(
                engine, self.max_slots, bool(getattr(self, "exemplar_device", False))
            )
            self._exemplar_loop = loop
        loop.sync_store()
        sched = loop.sched
        done: list[ExemplarRequest] = []
        free = sched.free_slots()
        if free and adm.pending:
            if sched.busy:
                wave = adm.claim(len(free), now, mid_wave=True)
            elif drain:
                wave = adm.claim(len(free), now, force=True)
            else:
                wave = adm.claim(len(free), now)
            for req in wave:
                st = new_query_state(BatchQuery(req.predicates, req.k, req.op))
                if st.done:  # k <= 0: satisfied with zero rows, never seats
                    req.result = finalize_query_result(engine, st)
                    req.done = True
                    done.append(req)
                    continue
                slot = sched.join((req, st))
                if loop.dwave is not None:
                    loop.dwave.join(slot, st)
        # prefetch overlap: predict the STILL-PENDING requests' round-0
        # union (they are the next wave) and start warming it now, while
        # this round plans/executes — its reads land on this tick, OUTSIDE
        # the demand window below, so the predicted wave's first fetch is a
        # pure tier hit and its priced I/O is 0
        pf = self._tier_prefetcher(engine)
        if pf is not None:
            pf.drain()
            pf.kick(adm.peek_pending(self.max_slots))
        if not sched.busy:
            return done
        cache = engine.block_cache
        hits0 = cache.stats.hits
        store0 = cache.stats.store_blocks_fetched
        tier_fn = getattr(cache, "tier_counters", None)
        tier0 = tier_fn() if tier_fn is not None else None
        transfers0 = loop.dwave.transfers if loop.dwave is not None else 0
        touched0 = len(loop.touched)
        missed: list[np.ndarray] = []  # DEMAND reads only (prefetch ran above)
        prev_log, cache.fetch_log = cache.fetch_log, missed
        try:
            if loop.dwave is not None:
                active, wave_blocks = loop.dwave.plan_round()
            else:
                active = [sched.slots[s][1] for s in sched.busy_slots()]
                wave_blocks = plan_round_host(
                    engine, active, "auto", getattr(engine, "distributed", None)
                )
            _execute_wave(
                engine, cache, active, wave_blocks, loop.touched, loop.touched_set
            )
        finally:
            cache.fetch_log = prev_log
        sched.tick()
        for slot in sched.busy_slots():
            req, st = sched.slots[slot]
            # a state at the refill cap leaves with what it has — exactly
            # where the solo loop would have stopped (waves < max_refills)
            if st.done or st.rounds >= engine.max_refills:
                req.result = finalize_query_result(engine, st)
                req.done = True
                sched.leave(slot)
                if loop.dwave is not None:
                    loop.dwave.leave(slot)
                done.append(req)
        union = (
            np.unique(np.concatenate(wave_blocks))
            if any(b.size for b in wave_blocks)
            else np.asarray([], dtype=np.int64)
        )
        if pf is not None:
            pf.observe_wave(union)
        # close the plan ledger's wave: per-tier predicted-vs-observed totals
        # snapshot into its audit trail, running q-error surfaces per wave
        lg = getattr(engine, "ledger", None)
        if lg is not None:
            lg.note_wave()
        from repro.obs.wave_stats import make_wave_stats

        self.last_wave_stats = make_wave_stats(
            "exemplar",
            wave_size=len(active),
            rounds=1,
            device_transfers=(
                (loop.dwave.transfers - transfers0) if loop.dwave is not None else 0
            ),
            store_blocks_fetched=int(cache.stats.store_blocks_fetched - store0),
            cache_hits=int(cache.stats.hits - hits0),
            unique_blocks=len(loop.touched) - touched0,
            tiers=(
                {k: v - tier0[k] for k, v in tier_fn().items()}
                if tier0 is not None
                else None
            ),
            slot_occupancy=sched.occupancy,
            modeled_store_io_s=sum(engine.cost.io_time(m) for m in missed),
            pending=adm.pending,
            prefetch=pf.stats.snapshot() if pf is not None else None,
            plan_qerror=lg.qerror(site="placement") if lg is not None else None,
        )
        self._note_wave_stats()
        return done

    def _aggregate_admission(self) -> AdmissionController:
        """The aggregate admission controller, created lazily for engines
        built without ``__init__`` (test shims)."""
        adm = getattr(self, "aggregate_admission", None)
        if adm is None:
            adm = AdmissionController(AdmissionPolicy(max_wave=self.max_slots),
                                      obs=getattr(self, "obs", None))
            self.aggregate_admission = adm
        return adm

    def submit_aggregate_request(
        self,
        predicates,
        measure: int,
        k: int,
        *,
        op: str = "and",
        error_slo: float | None = None,
        deadline_s: float | None = None,
        alpha: float = 0.3,
        estimator: str = "ratio",
        algo: str = "threshold",
        seed: int = 0,
        chunk_blocks: int = 8,
        max_rounds: int = 64,
    ) -> AggregateRequest:
        """Admit an online aggregate under the SLO policy; it seats in the
        continuous loop's aggregate pool and streams an Estimate per round
        until its SLO answers it."""
        req = AggregateRequest(
            next(self._rid), predicates, measure, k, op,
            error_slo=error_slo, deadline_s=deadline_s, alpha=alpha,
            estimator=estimator, algo=algo, seed=seed,
            chunk_blocks=chunk_blocks, max_rounds=max_rounds,
        )
        obs = getattr(self, "obs", None)
        if obs is not None:
            obs.event("request.submit", rid=req.rid, kind="aggregate")
            obs.metrics.inc("serve.aggregate.submitted")
        self._aggregate_admission().submit(req)
        return req

    def aggregate_tick(
        self, engine, now: float | None = None, drain: bool = False
    ) -> list[AggregateRequest]:
        """One round of the continuous online-aggregation loop.

        The aggregate counterpart of :meth:`exemplar_tick`: freed slots are
        refilled from the aggregate admission queue mid-wave, every busy
        slot stages its next chunk (one shared deduplicated ``ensure`` pays
        the union fetch), folds it through its
        :class:`~repro.core.online_agg.OnlineAggregator`, and then the
        third arbitration arm (:func:`repro.serving.admission.
        arbitrate_aggregate`) decides answer-now vs fetch-more per slot —
        priced by :func:`repro.storage.prefetch.effective_block_cost`, the
        same ``TierStack.effective_io_time`` probe cost-fed admission uses.
        An error-SLO request whose CI closes leaves its slot THIS tick
        (mid-wave, like a k-satisfied exemplar); ``last_wave_stats`` records
        each leave under ``"answered"`` (rid / reason / rounds / halfwidth).
        Returns the requests answered this tick.
        """
        self._wire_obs(engine)
        obs = getattr(self, "obs", None)
        if obs is None:
            return self._aggregate_tick_body(engine, now, drain)
        with obs.span("serve.aggregate_tick") as sp:
            done = self._aggregate_tick_body(engine, now, drain)
            sp.set(completed=len(done))
            for req in done:
                obs.event("request.done", rid=req.rid, kind="aggregate",
                          rounds=req.rounds, reason=req.reason)
        return done

    def _aggregate_tick_body(
        self, engine, now: float | None, drain: bool
    ) -> list[AggregateRequest]:
        from repro.core.online_agg import AggregateQuery, OnlineAggregator
        from repro.serving.admission import arbitrate_aggregate
        from repro.storage.prefetch import effective_block_cost

        adm = self._aggregate_admission()
        loop = self._aggregate_loop
        if (
            loop is None
            or loop.engine is not engine
            or loop.sched.n_slots != self.max_slots
        ):
            if loop is not None:  # stranded on a stale engine: answer as-is
                for slot in loop.sched.busy_slots():
                    req, agg = loop.sched.slots[slot]
                    if agg.estimates:
                        req.result = agg.estimates[-1]
                    req.reason, req.done = "budget", True
                    agg.close()
            loop = _AggregateLoop(engine, self.max_slots)
            self._aggregate_loop = loop
        sched = loop.sched
        done: list[AggregateRequest] = []
        free = sched.free_slots()
        if free and adm.pending:
            if sched.busy:
                wave = adm.claim(len(free), now, mid_wave=True)
            elif drain:
                wave = adm.claim(len(free), now, force=True)
            else:
                wave = adm.claim(len(free), now)
            for req in wave:
                q = AggregateQuery(
                    req.predicates, req.measure, req.k, alpha=req.alpha,
                    op=req.op, estimator=req.estimator, algo=req.algo,
                    seed=req.seed,
                )
                agg = OnlineAggregator(engine, q, chunk_blocks=req.chunk_blocks)
                sched.join((req, agg))
        if not sched.busy:
            return done
        cache = engine.block_cache
        hits0 = cache.stats.hits
        store0 = cache.stats.store_blocks_fetched
        tier_fn = getattr(cache, "tier_counters", None)
        tier0 = tier_fn() if tier_fn is not None else None
        # stage every slot's chunk and price it BEFORE the shared fetch —
        # the demand price a solo run would have paid for that chunk
        staged: dict[int, tuple[np.ndarray, float]] = {}
        for slot in sched.busy_slots():
            _, agg = sched.slots[slot]
            chunk = agg.next_blocks()
            staged[slot] = (chunk, effective_block_cost(engine, chunk))
        union = (
            np.unique(np.concatenate([c for c, _ in staged.values()]))
            if any(c.size for c, _ in staged.values())
            else np.asarray([], dtype=np.int64)
        )
        missed: list[np.ndarray] = []
        prev_log, cache.fetch_log = cache.fetch_log, missed
        try:
            if union.size:
                cache.ensure(engine.store, union)
            for slot in sorted(staged):
                req, agg = sched.slots[slot]
                e = agg.fold()
                agg.spent_io_s += staged[slot][1]
                req.stream.append(e)
                req.rounds = agg.rounds
                req.spent_io_s = agg.spent_io_s
        finally:
            cache.fetch_log = prev_log
        sched.tick()
        answered: list[dict] = []
        for slot in sched.busy_slots():
            req, agg = sched.slots[slot]
            nxt = agg.next_blocks()  # peek the following chunk's price
            verdict = arbitrate_aggregate(
                halfwidth=agg.halfwidth(),
                error_slo=req.error_slo,
                deadline_s=req.deadline_s,
                spent_s=agg.spent_io_s,
                next_cost_s=effective_block_cost(engine, nxt),
                predicted_halfwidth=agg.predicted_halfwidth(agg.chunk_blocks),
                max_s_per_width=getattr(self, "aggregate_max_s_per_width", None),
            )
            if verdict is None and agg.exhausted:
                verdict = "exhausted"
            if verdict is None and agg.rounds >= req.max_rounds:
                verdict = "budget"
            if verdict is not None:
                req.result = agg.estimates[-1]
                req.reason = verdict
                req.done = True
                agg.close()
                sched.leave(slot)
                done.append(req)
                answered.append({
                    "rid": req.rid,
                    "reason": verdict,
                    "rounds": agg.rounds,
                    "halfwidth": agg.halfwidth(),
                })
        from repro.obs.wave_stats import make_wave_stats

        self.last_wave_stats = make_wave_stats(
            "aggregate",
            wave_size=len(staged),
            rounds=1,
            store_blocks_fetched=int(cache.stats.store_blocks_fetched - store0),
            cache_hits=int(cache.stats.hits - hits0),
            unique_blocks=int(union.size),
            tiers=(
                {k: v - tier0[k] for k, v in tier_fn().items()}
                if tier0 is not None
                else None
            ),
            slot_occupancy=sched.occupancy,
            modeled_store_io_s=sum(engine.cost.io_time(m) for m in missed),
            pending=adm.pending,
            answered=answered,
        )
        self._note_wave_stats()
        return done

    def lm_tick(self) -> list[Request]:
        """One tick of the continuous LM decode loop.

        First tick of an empty pool prefills a fresh wave exactly like
        :meth:`_run_wave` (same left-padding, same first argmax token — the
        token streams are byte-identical to the wave path).  Every later
        tick first seats eligible queued joiners — a joiner's prompt must
        fit the shared position counter (``len(prompt) <= pos``): it is
        left-padded to exactly ``pos``, prefilled as its own batch, and its
        cache rows grafted into the live wave's (:func:`_merge_lm_cache_rows`
        — batch rows are independent, so the graft changes nothing for
        incumbents and gives the joiner the same state a solo run at that
        padding would) — then decodes ONE step and retires slots on
        EOS/``max_new_tokens`` immediately, freeing them for the next tick's
        joiners.  Returns the requests completed this tick.

        A tick that actually ran (prefill or decode step) writes a
        ``kind="lm"`` wave ledger to ``last_wave_stats`` — the same closed
        schema as the exemplar/aggregate pools (:mod:`repro.obs.wave_stats`);
        I/O-plane keys stay at their zero defaults (the LM pool does no
        block I/O).
        """
        obs = getattr(self, "obs", None)
        if obs is None:
            return self._lm_tick_body()
        with obs.span("serve.lm_tick") as sp:
            done = self._lm_tick_body()
            sp.set(completed=len(done))
            for req in done:
                obs.event("request.done", rid=req.rid, kind="lm",
                          tokens=len(req.out_tokens))
        return done

    def _note_lm_wave(self, wave_size: int) -> None:
        """One LM tick's wave ledger (schema-complete, metrics-mirrored)."""
        from repro.obs.wave_stats import make_wave_stats

        self.last_wave_stats = make_wave_stats(
            "lm",
            wave_size=wave_size,
            rounds=1,
            slot_occupancy=wave_size / max(self.max_slots, 1),
            pending=len(self.queue),
        )
        self._note_wave_stats()

    def _lm_tick_body(self) -> list[Request]:
        if self._prefill is None:
            return []
        done: list[Request] = []
        if self._lm is None:
            if not self.queue:
                return []
            wave = self._next_wave()
            plen = max(len(r.prompt) for r in wave)
            toks = np.full((self.max_slots, plen), self.pad_id, np.int32)
            for b, r in enumerate(wave):  # left-pad: align last prompt token
                toks[b, plen - len(r.prompt):] = r.prompt
            last, cache = self._prefill(self.params, jnp.asarray(toks))
            nxt = np.asarray(jnp.argmax(last, axis=-1))
            slots: list[Request | None] = [None] * self.max_slots
            for b, r in enumerate(wave):
                r.out_tokens.append(int(nxt[b]))
                slots[b] = r
            self._lm = {"cache": cache, "pos": plen, "slots": slots}
            self._note_lm_wave(len(wave))
            return done  # prefill is the tick; first decode lands next tick
        lm = self._lm
        pos = int(lm["pos"])
        slots: list[Request | None] = lm["slots"]
        free = [b for b, s in enumerate(slots) if s is None]
        joiners: list[tuple[int, Request]] = []
        while free and self.queue and len(self.queue[0].prompt) <= pos:
            req = self.queue.popleft()
            b = free.pop(0)
            slots[b] = req
            joiners.append((b, req))
        if joiners:
            toks = np.full((self.max_slots, pos), self.pad_id, np.int32)
            for b, r in joiners:
                toks[b, pos - len(r.prompt):] = r.prompt
            last, cache_j = self._prefill(self.params, jnp.asarray(toks))
            mask = np.zeros(self.max_slots, bool)
            for b, _ in joiners:
                mask[b] = True
            lm["cache"] = _merge_lm_cache_rows(lm["cache"], cache_j, mask)
            nxt = np.asarray(jnp.argmax(last, axis=-1))
            for b, r in joiners:
                r.out_tokens.append(int(nxt[b]))
        active = [b for b, s in enumerate(slots) if s is not None]
        if not active or pos >= self.max_seq - 1:
            for b in active:  # sequence budget exhausted: retire as-is
                slots[b].done = True
                done.append(slots[b])
                slots[b] = None
            self._lm = None
            self._note_lm_wave(len(active))
            return done
        cur = np.full(self.max_slots, self.pad_id, np.int32)
        for b in active:
            cur[b] = slots[b].out_tokens[-1]
        logits, cache = self._decode(
            self.params, lm["cache"], jnp.asarray(cur), jnp.int32(pos)
        )
        lm["cache"] = cache
        nxt = np.asarray(jnp.argmax(logits, axis=-1))
        lm["pos"] = pos + 1
        for b in active:
            r = slots[b]
            tok = int(nxt[b])
            r.out_tokens.append(tok)
            # retire check only AFTER the decode append — mirrors _run_wave
            # (max_new_tokens=1 still yields 2 tokens), so the continuous
            # and wave paths emit identical streams
            if (self.eos_id is not None and tok == self.eos_id) or len(
                r.out_tokens
            ) >= r.max_new_tokens:
                r.done = True
                slots[b] = None
                done.append(r)
        if all(s is None for s in slots):
            self._lm = None
        self._note_lm_wave(len(active))
        return done

    def step(
        self, engine=None, now: float | None = None, drain: bool = False
    ) -> dict:
        """One continuous-batching tick over ALL request kinds: the LM
        decode pool advances one token (joiners seated first) and, when an
        any-k `engine` is given, the exemplar pool runs one refill round and
        the online-aggregation pool one fold round (freed slots refilled
        mid-wave in both).  Returns ``{"lm": [completed Requests],
        "exemplar": [completed ExemplarRequests], "aggregate": [answered
        AggregateRequests]}``.  ``last_wave_stats`` reflects the last pool
        that actually ran a round this tick (the aggregate ledger carries
        ``"kind": "aggregate"``)."""
        out = {"lm": [], "exemplar": [], "aggregate": []}
        with span(getattr(self, "obs", None), "serve.step"):
            if self._prefill is not None and (self.queue or self._lm is not None):
                out["lm"] = self.lm_tick()
            if engine is not None:
                out["exemplar"] = self.exemplar_tick(engine, now=now, drain=drain)
                out["aggregate"] = self.aggregate_tick(engine, now=now, drain=drain)
        return out

    def run_continuous(self, engine=None, max_ticks: int = 100_000,
                       drain: bool = True) -> dict:
        """Tick :meth:`step` until both pools and queues are empty (or the
        loop stalls — ``drain=False`` with a holding admission policy).
        The continuous counterpart of :meth:`run_until_drained` +
        :meth:`drain_exemplar_requests`; returns all completions keyed like
        :meth:`step`."""
        lm_done: list[Request] = []
        ex_done: list[ExemplarRequest] = []
        agg_done: list[AggregateRequest] = []
        adm = self._exemplar_admission() if engine is not None else None
        agg_adm = self._aggregate_admission() if engine is not None else None

        def signature():
            loop = self._exemplar_loop
            aloop = self._aggregate_loop
            return (
                adm.pending if adm is not None else 0,
                loop.sched.rounds if loop is not None else 0,
                agg_adm.pending if agg_adm is not None else 0,
                aloop.sched.rounds if aloop is not None else 0,
                len(self.queue),
                int(self._lm["pos"]) if self._lm is not None else -1,
            )

        for _ in range(max_ticks):
            lm_busy = self._prefill is not None and (
                bool(self.queue) or self._lm is not None
            )
            loop = self._exemplar_loop
            ex_busy = engine is not None and (
                adm.pending > 0
                or (loop is not None and loop.engine is engine and loop.sched.busy > 0)
            )
            aloop = self._aggregate_loop
            agg_busy = engine is not None and (
                agg_adm.pending > 0
                or (
                    aloop is not None
                    and aloop.engine is engine
                    and aloop.sched.busy > 0
                )
            )
            if not lm_busy and not ex_busy and not agg_busy:
                break
            sig = signature()
            out = self.step(engine, drain=drain)
            lm_done.extend(out["lm"])
            ex_done.extend(out["exemplar"])
            agg_done.extend(out["aggregate"])
            if (
                not out["lm"]
                and not out["exemplar"]
                and not out["aggregate"]
                and signature() == sig
            ):
                break  # stalled: nothing moved and nothing finished
        return {"lm": lm_done, "exemplar": ex_done, "aggregate": agg_done}
