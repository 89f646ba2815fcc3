"""Incremental index maintenance: append records to a BlockStore without a
full rebuild (production corpora grow; the paper assumes read-mostly data and
builds at load time — this is the write path that keeps its invariants).

Only the trailing partial block and the newly created blocks have their
density-map columns recomputed; untouched column prefixes are reused.  The
per-row *sorted* density maps are re-sorted (argsort over λ — O(λ log λ) per
touched row, still ≪ a rebuild which rescans all N records).

:func:`rebuild_store` is the shared re-blocking core: append (this module)
and tail compaction (:mod:`repro.storage.compact`) both hand it flattened
valid rows plus the set of touched block ids and get back a successor store
with listeners carried over — the caller decides what is dirty and notifies.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.density_map import DensityMapIndex
from repro.data.block_store import BlockStore, Table, blocked_layout


def dirtied_block_ids(store: BlockStore, num_new: int) -> np.ndarray:
    """Block ids an append of ``num_new`` records rewrites or creates: the
    trailing partial block plus every newly created block.  This is exactly
    the id range whose cached slabs / density columns go stale."""
    rpb = store.records_per_block
    first_touched = store.num_records // rpb
    lam_new = -(-(store.num_records + num_new) // rpb)
    return np.arange(first_touched, lam_new, dtype=np.int64)


def rebuild_store(
    store: BlockStore,
    dims_flat: np.ndarray,
    meas_flat: np.ndarray,
    touched: np.ndarray,
) -> BlockStore:
    """Re-block flattened valid rows into a successor of ``store``.

    Same schema and records-per-block; density columns are recomputed only
    for the ``touched`` block ids (column prefixes before the first touched
    id are reused from ``store.index``), and invalidation listeners are
    carried over.  Callers notify ``store``'s listeners with the dirtied id
    set themselves — append and compaction decide what is dirty.
    """
    rpb = store.records_per_block
    n = dims_flat.shape[0]
    r = dims_flat.shape[1]
    dims_b, meas_b, valid_b = blocked_layout(dims_flat, meas_flat, rpb)
    lam_new = dims_b.shape[0]

    # density columns: reuse untouched prefix, recompute only touched blocks
    idx = store.index
    old_dens = np.asarray(idx.densities)
    touched = np.asarray(touched, dtype=np.int64)
    first_touched = int(touched[0]) if touched.size else lam_new
    dens = np.zeros((idx.vocab.num_rows, lam_new), np.float32)
    dens[:, :first_touched] = old_dens[:, :first_touched]
    off = idx.vocab.attr_offsets
    for b in touched:
        blk = dims_b[b]
        for attr in range(r):
            vals, counts = np.unique(blk[:, attr], return_counts=True)
            for v, c in zip(vals, counts):
                if v >= 0:
                    dens[off[attr] + v, b] = c / rpb
    order = np.argsort(-dens, axis=1, kind="stable").astype(np.int32)
    sdens = np.take_along_axis(dens, order, axis=1)
    new_index = DensityMapIndex(
        vocab=idx.vocab,
        densities=jnp.asarray(dens),
        sorted_block_ids=jnp.asarray(order),
        sorted_densities=jnp.asarray(sdens),
        records_per_block=rpb,
        num_records=n,
    )
    rebuilt = BlockStore(
        dims=dims_b,
        measures=meas_b,
        valid_rows=valid_b,
        index=new_index,
        records_per_block=rpb,
        num_records=n,
    )
    rebuilt._invalidation_listeners = list(store._invalidation_listeners)
    return rebuilt


def append_records(store: BlockStore, new: Table) -> BlockStore:
    """Returns a new BlockStore with `new` rows appended (same schema).

    Invalidation hook: listeners registered on ``store`` (see
    :meth:`BlockStore.register_invalidation_listener`) are notified with the
    dirtied tail block ids — only the trailing partial block and the newly
    created blocks — and are carried over to the returned store, so an
    engine-lifetime block cache survives the append with surgical eviction.
    """
    old_n = store.num_records
    dims_flat = np.concatenate([
        store.dims.reshape(-1, store.dims.shape[-1])[:old_n],
        new.dims.astype(np.int32),
    ])
    meas_flat = np.concatenate([
        store.measures.reshape(-1, store.measures.shape[-1])[:old_n],
        new.measures.astype(np.float32),
    ])
    touched = dirtied_block_ids(store, new.num_records)
    grown = rebuild_store(store, dims_flat, meas_flat, touched)
    store.notify_invalidated(touched)
    return grown
