"""Block-oriented storage (paper §3: block-level reasoning).

A :class:`Table` is the logical star-schema table (dimension attributes +
measures).  A :class:`BlockStore` is its physical layout: fixed-size blocks of
``records_per_block`` rows — the TPU analogue of the paper's 256 KB disk block.
The host keeps them as row-major ``[λ, R, ·]`` numpy slabs; the device copy is
lane-dense ``[λ, ·, R]`` (see :class:`BlockStore`).

Fetches go through :meth:`BlockStore.fetch`, which returns the block slab plus a
validity mask; the engine charges I/O for fetched blocks through the cost model.
"""
from __future__ import annotations

import dataclasses
import functools
import weakref
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.density_map import AND, OR, DensityMapIndex, build_density_maps


@dataclasses.dataclass
class Table:
    dims: np.ndarray  # [N, r] int32 dimension attributes
    measures: np.ndarray  # [N, s] float32 measure attributes
    cards: np.ndarray  # [r] distinct-value counts

    @property
    def num_records(self) -> int:
        return int(self.dims.shape[0])

    def valid_mask(self, predicates: Sequence[tuple[int, int]], op: str = AND) -> np.ndarray:
        masks = [self.dims[:, a] == v for a, v in predicates]
        m = np.logical_and.reduce(masks) if op == AND else np.logical_or.reduce(masks)
        return m


@dataclasses.dataclass
class BlockStore:
    """Physical blocked layout + the DensityMap index built at load time.

    ``dims`` / ``measures`` / ``valid_rows`` are the host slabs that
    :meth:`fetch` serves.  Construction uploads a device copy in a
    lane-dense layout, the record axis R minor: ``dims_dev [λ, r, R]`` and
    ``meas_dev [λ, s, R]``.  TPU memory tiles the two minor dims by
    (8, 128), so a row-major ``[λ, R, r]`` tensor would pad r = 8 to 128
    lanes (16x: 51 GB for the paper's 100M-record table); with R minor the
    copy costs its logical size.  Row validity is not stored on the device:
    the valid rows are exactly the first ``num_records`` of the flattened
    layout, so :meth:`gather_device` derives the mask from the block ids.
    """

    dims: np.ndarray  # [lam, R, r] int32, padded with -1 (matches no value)
    measures: np.ndarray  # [lam, R, s] f32, padded with 0
    valid_rows: np.ndarray  # [lam, R] bool, False on padding
    index: DensityMapIndex
    records_per_block: int
    num_records: int

    @property
    def num_blocks(self) -> int:
        return int(self.dims.shape[0])

    def __post_init__(self):
        self.dims_dev = jnp.asarray(np.ascontiguousarray(self.dims.transpose(0, 2, 1)))
        self.meas_dev = jnp.asarray(
            np.ascontiguousarray(self.measures.transpose(0, 2, 1))
        )
        # callbacks fired with the dirtied block ids when the write path
        # (repro.data.append) rewrites blocks of this store's lineage
        self._invalidation_listeners: list = []

    # --------------------------------------------------- cache invalidation
    def register_invalidation_listener(self, callback) -> None:
        """Register ``callback(block_ids)`` to run when blocks are rewritten.

        The append path (:func:`repro.data.append.append_records`) notifies
        with exactly the dirtied tail block ids, so an engine-lifetime block
        cache can evict surgically instead of flushing wholesale.  Listeners
        are carried over to the successor store the append returns.  Bound
        methods are held weakly: a store outlives throwaway engines, and a
        strong ref here would pin every dead engine's whole block cache.
        """
        if any(ref() == callback for ref in self._invalidation_listeners):
            return
        if hasattr(callback, "__self__"):
            ref = weakref.WeakMethod(callback)
        else:  # plain function/lambda: keep strong (nothing big to pin)
            ref = lambda cb=callback: cb  # noqa: E731
        self._invalidation_listeners.append(ref)

    def unregister_invalidation_listener(self, callback) -> None:
        self._invalidation_listeners = [
            ref for ref in self._invalidation_listeners
            if ref() is not None and ref() != callback
        ]

    def notify_invalidated(self, block_ids: np.ndarray) -> None:
        alive = []
        for ref in self._invalidation_listeners:
            cb = ref()
            if cb is not None:
                cb(np.asarray(block_ids, dtype=np.int64))
                alive.append(ref)
        self._invalidation_listeners = alive

    def fetch(self, block_ids: np.ndarray) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Gather block slabs: (dims [B,R,r], measures [B,R,s], row_valid [B,R])."""
        ids = np.asarray(block_ids, dtype=np.int64)
        return self.dims[ids], self.measures[ids], self.valid_rows[ids]

    def gather_device(self, block_ids) -> tuple[jax.Array, jax.Array, jax.Array, int]:
        """Lane-dense device union gather: one jitted launch of the
        :func:`repro.kernels.plan_wave.block_gather` Pallas kernel per tensor
        (scalar-prefetched ids drive the gather ``index_map``).

        Returns ``(dims [Ub, r, R], meas [Ub, s, R], valid [Ub, R], U)``.
        The ids are padded to ``Ub``, the next power of two ≥ U, so a stream
        of unions of varying size compiles once per bucket, not once per
        size; rows ``U:`` repeat block 0 and carry no meaning.  Row ``i < U``
        holds block ``block_ids[i]``, each record a column, byte-identical
        to ``fetch(block_ids)[·][i]`` transposed.
        """
        ids = np.asarray(block_ids, dtype=np.int32).ravel()
        u = int(ids.size)
        ub = 1 << max(u - 1, 0).bit_length()
        padded = np.zeros((ub,), np.int32)
        padded[:u] = ids
        dd, dm, dv = _gather_lane_dense(
            self.dims_dev, self.meas_dev, jnp.asarray(padded),
            jnp.int32(self.num_records),
            interpret=jax.default_backend() != "tpu",
        )
        return dd, dm, dv, u

    def fetch_device(self, block_ids) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Device-resident union fetch: :meth:`gather_device` transposed back
        to the row-major ``(dims [U,R,r], measures [U,R,s], valid [U,R])``
        that :meth:`fetch` returns, byte for byte.

        The device-side counterpart of :meth:`fetch` for consumers that keep
        the slabs on device: no host mirror is materialized, so it adds zero
        device→host transfers.  The tiered storage hierarchy fills its HBM
        tier through :meth:`gather_device` (lane-dense slabs), and device
        consumers read that residency back through
        :meth:`repro.storage.tiers.TierStack.get_device`.
        """
        return _row_major(*self.gather_device(block_ids))

    def predicate_mask(
        self, block_dims, predicates: Sequence[tuple[int, int]], op: str = AND
    ):
        """[B, R] bool — which records in the fetched blocks satisfy the query."""
        masks = [block_dims[..., a] == v for a, v in predicates]
        out = masks[0]
        for m in masks[1:]:
            out = (out & m) if op == AND else (out | m)
        return out

    def data_nbytes(self) -> int:
        return int(self.dims.size * 4 + self.measures.size * 4)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather_lane_dense(dims_dev, meas_dev, ids, num_records, interpret: bool):
    from repro.kernels.plan_wave import block_gather

    rpb = dims_dev.shape[-1]
    row = ids[:, None] * rpb + jnp.arange(rpb, dtype=jnp.int32)[None, :]
    return (
        block_gather(dims_dev, ids, interpret=interpret),
        block_gather(meas_dev, ids, interpret=interpret),
        row < num_records,
    )


@functools.partial(jax.jit, static_argnums=(3,))
def _row_major(dd, dm, dv, u: int):
    return dd[:u].transpose(0, 2, 1), dm[:u].transpose(0, 2, 1), dv[:u]


def blocked_layout(
    dims: np.ndarray, measures: np.ndarray, records_per_block: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Flat ``[N, r]`` / ``[N, s]`` rows -> the host slabs of a
    :class:`BlockStore`: ``(dims [λ, R, r] int32 padded with -1,
    measures [λ, R, s] f32 padded with 0, valid [λ, R] bool)``.

    Each output is allocated once at its padded size and filled in place
    (one host copy of the table, whatever the input dtype).
    """
    n = dims.shape[0]
    rpb = records_per_block
    lam = -(-n // rpb)

    def blocked(a: np.ndarray, fill, dtype) -> np.ndarray:
        out = np.full((lam * rpb,) + a.shape[1:], fill, dtype)
        out[:n] = a
        return out.reshape((lam, rpb) + a.shape[1:])

    valid = np.zeros((lam * rpb,), bool)
    valid[:n] = True
    return (
        blocked(dims, -1, np.int32),
        blocked(measures, 0.0, np.float32),
        valid.reshape(lam, rpb),
    )


def build_block_store(table: Table, records_per_block: int) -> BlockStore:
    index = build_density_maps(table.dims, table.cards, records_per_block)
    dims, meas, valid = blocked_layout(table.dims, table.measures, records_per_block)
    return BlockStore(
        dims=dims,
        measures=meas,
        valid_rows=valid,
        index=index,
        records_per_block=records_per_block,
        num_records=table.num_records,
    )
