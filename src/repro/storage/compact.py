"""Background compaction: re-sort the appended tail by density (§4.1 locality).

`append_records` keeps the density index byte-correct but leaves the new
rows wherever they arrived — after heavy appends the tail interleaves
values, so the dense, contiguous block prefixes the THRESHOLD/TWO-PRONG
planners and `TierPrefetcher` assume degrade into scattered sparse blocks.
This module restores them between waves:

* :func:`compact_tail` re-sorts the valid rows of every block from
  ``tail_start`` on lexicographically by their dimension values (attribute
  0 major — the clustering the loaders produce), re-blocks them through
  the same :func:`repro.data.append.rebuild_store` core as append, and
  **notifies the standard invalidation listeners** with the rewritten id
  range — so block caches, tier stacks, peer directories, and plan memos
  all drop the stale bytes exactly like they do on append.
* :class:`TailCompactor` is the between-waves driver: it watches the
  engine's store for append invalidations, remembers the dirty low-water
  mark, and on :meth:`TailCompactor.compact` rewrites that tail and swaps
  the engine onto the compacted store (mirroring the adoption contract of
  ``NeedleTailEngine.append``).

Compaction *permutes* tail rows: the compacted store is a new store
version, and results match the sequential oracle **on that version** —
the same per-store-version equivalence append already has.  Bytes served
for any fixed store version never change.
"""
from __future__ import annotations

import weakref

import numpy as np

from repro.data.append import rebuild_store

__all__ = ["compact_tail", "TailCompactor"]


def compact_tail(store, tail_start: int):
    """Return a successor of `store` whose blocks ≥ `tail_start` are re-sorted.

    Valid rows of the tail are ordered lexicographically by dimension values
    (attribute 0 major) so equal values land in dense contiguous runs; rows
    before ``tail_start * records_per_block`` keep their exact layout, and
    density columns for the untouched prefix are reused.  Listeners on
    `store` are notified with the rewritten id range and carried over.
    """
    rpb = store.records_per_block
    n = store.num_records
    lam = store.num_blocks
    tail_start = int(tail_start)
    if not (0 <= tail_start < lam):
        raise ValueError(f"tail_start {tail_start} outside [0, {lam})")
    dims_flat = store.dims.reshape(-1, store.dims.shape[-1])[:n]
    meas_flat = store.measures.reshape(-1, store.measures.shape[-1])[:n]
    lo = tail_start * rpb
    # lexsort's last key is the primary: feed columns reversed so attr 0 is major
    order = np.lexsort(dims_flat[lo:].T[::-1])
    dims_flat = np.concatenate([dims_flat[:lo], dims_flat[lo:][order]])
    meas_flat = np.concatenate([meas_flat[:lo], meas_flat[lo:][order]])
    touched = np.arange(tail_start, lam, dtype=np.int64)
    fresh = rebuild_store(store, dims_flat, meas_flat, touched)
    store.notify_invalidated(touched)
    return fresh


class TailCompactor:
    """Between-waves compaction driver for a `NeedleTailEngine`.

    Registers an invalidation listener on the engine's store (re-registered
    whenever the engine adopts a successor store, like `TierPrefetcher`)
    and tracks the lowest dirtied block id since the last compaction.
    :meth:`compact` rewrites that tail via :func:`compact_tail` and swaps
    the engine onto the compacted store through ``engine.compact`` — its
    own rewrite notification is suppressed from the dirty tracking so a
    compaction does not schedule itself again.
    """

    def __init__(self, engine):
        self._engine_ref = weakref.ref(engine)
        self._store = None
        self.dirty_since: int | None = None
        self.compactions = 0
        self._suspend = False
        self._sync_store()

    # -- store tracking (the engine swaps stores on append/compact/replace) --
    def _sync_store(self) -> None:
        eng = self._engine_ref()
        if eng is None or eng.store is self._store:
            return
        if self._store is not None:
            self._store.unregister_invalidation_listener(self._on_invalidate)
        self._store = eng.store
        self._store.register_invalidation_listener(self._on_invalidate)

    def _on_invalidate(self, block_ids) -> None:
        if self._suspend:
            return
        ids = np.asarray(list(block_ids), dtype=np.int64)
        if ids.size == 0:
            return
        low = int(ids.min())
        self.dirty_since = low if self.dirty_since is None else min(self.dirty_since, low)

    # ----------------------------------------------------------------- drive
    def pending_blocks(self) -> int:
        """Blocks the next compact() would rewrite (0 = tail is clean)."""
        eng = self._engine_ref()
        if eng is None or self.dirty_since is None:
            return 0
        self._sync_store()
        return max(eng.store.num_blocks - min(self.dirty_since, eng.store.num_blocks), 0)

    def compact(self, min_blocks: int = 1) -> int:
        """Compact the dirty tail if it spans ≥ `min_blocks`; returns blocks rewritten."""
        eng = self._engine_ref()
        if eng is None:
            return 0
        self._sync_store()
        n = self.pending_blocks()
        if n < max(int(min_blocks), 1):
            return 0
        tail_start = eng.store.num_blocks - n
        self._suspend = True
        try:
            eng.compact(tail_start)
        finally:
            self._suspend = False
        self.dirty_since = None
        self.compactions += 1
        self._sync_store()
        return n
