"""Embedding-bag (sum-pooled sparse embedding lookup) Pallas kernels.

The trainer-side hot spot of DLRM: for each sample, gather ``nnz`` rows of an
embedding table and sum-pool them.  The ETL engine feeds bounded int32 indices
(VocabMap output), and these kernels are what consume them on the training
chip.

Two levels (the BagPipe/Hotline popular-rare split, PAPERS.md):

- ``embedding_bag`` — the uncached baseline.  The table is partitioned across
  the grid (same "HBM banks" pattern as vocab.py): each grid step loads one
  table partition into VMEM and resolves the in-partition indices.  This
  turns an irregular HBM gather into P dense VMEM passes — MXU/VPU friendly
  and deterministic, at the cost of a P-fold index scan (P is small: tables
  are partitioned only when they exceed the VMEM budget).
- ``embedding_bag_cached`` — the two-level cached form fed by the lookahead
  stage (``etl_runtime/lookahead.py``).  Hot indices arrive pre-remapped to
  slots of a small ``[cache_rows, dim]`` cache tensor that stays VMEM-resident
  for the whole grid (ONE dense pass, no table traffic); cold indices fall
  through the same partitioned table pass as the uncached kernel.  When the
  lookahead plan stages every cold row into the cache for the batch
  (``cold_idx=None``), the kernel is a single cache pass and never touches
  the table at all.

Both kernels share one structure so they are **bit-identical** on the same
logical indices: a gather phase materializes the per-(sample, k) rows tile —
each entry written by exactly one pass, so no float accumulation order is
involved — and one shared ``jnp`` sum pools over ``nnz``.  ``-1`` indices are
sentinels and contribute zero (packer padding / empty bag lanes).

Block shapes are hardware-tiled: the embedding ``dim`` is lane-padded to a
128-multiple (zero lanes, sliced off before pooling), index blocks carry
``nnz`` lane-padded with ``-1`` sentinels and the kernel slices them to the
sublane-padded ``nnz`` the 3-D rows tile uses, and partition row counts are
sublane-padded (padded rows are unreachable: indices are bounded by the
vocab and masked in-kernel).  The row gather reads each index as a scalar
and copies its row with a dynamic sublane index (``kernels.lanes``), the
form Mosaic lowers; Mosaic has no vector gather across vregs.

``interpret=None`` resolves through ``kernels.backend.default_interpret``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import lanes
from repro.kernels.backend import default_interpret


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pool(rows, batch: int, nnz: int, dim: int):
    """Shared pooling epilogue: slice off batch/nnz/dim padding, sum over nnz.

    Both kernels feed identical row tiles through this exact op, which is
    what makes cached-vs-uncached equality bit-level rather than allclose.
    """
    return rows[:batch, :nnz, :dim].sum(axis=1)


def _partitioned(table, partitions: int):
    """Split the vocab across ``partitions``, zero-padding the last partition
    (and rounding each partition up to the sublane tile) so arbitrary vocab
    sizes work; the dim axis is lane-padded.  Padded rows are unreachable:
    indices are bounded by the vocab and out-of-range values are masked
    in-kernel."""
    vocab, dim = table.shape
    p = max(partitions, 1)
    part = lanes.sublane_pad(-(-vocab // p))
    dim_pad = lanes.lane_pad(dim)
    table = jnp.pad(table, ((0, part * p - vocab), (0, dim_pad - dim)))
    return table, part, p, dim_pad


def _pad_batch(idx, block_batch: int):
    """Pad the batch axis to the block multiple and the nnz axis to the
    lane tile (with -1 sentinels, which every kernel masks out)."""
    batch, nnz = idx.shape
    bb = min(block_batch, _round_up(batch, 8))
    bp = _round_up(batch, bb)
    nnz_lane = lanes.lane_pad(nnz)
    idx = jnp.pad(idx, ((0, bp - batch), (0, nnz_lane - nnz)),
                  constant_values=-1)
    return idx, bb, bp, nnz_lane


def _gather_pass(local, src_ref, rows_ref):
    """``rows_ref[b, k] = src_ref[local[b, k]]`` wherever ``local[b, k] >=
    0``; other entries keep their value.  Each entry is read as a scalar
    (``lanes.for_each_row``) and copies one row with a dynamic sublane
    index — the row gather Mosaic lowers."""
    bb, nnz = local.shape

    def row_fn(b, at):
        def col(k, carry):
            v = at(k)
            ok = v >= 0
            row = src_ref[pl.ds(jnp.where(ok, v, 0), 1), :]
            cur = rows_ref[b, pl.ds(k, 1), :]
            rows_ref[b, pl.ds(k, 1), :] = jnp.where(ok, row, cur)
            return carry

        jax.lax.fori_loop(0, nnz, col, 0)

    pl.run_scoped(
        lambda stage_ref, smem_ref: lanes.for_each_row(
            local, row_fn, stage_ref, smem_ref),
        *lanes.scalar_scratch(bb, nnz))


def _gather_kernel(idx_ref, tbl_ref, rows_ref, *, part_rows: int, nnz: int):
    """One table-partition pass: write rows for in-partition indices."""
    p = pl.program_id(1)
    lo = p * part_rows

    @pl.when(p == 0)
    def _init():
        rows_ref[...] = jnp.zeros(rows_ref.shape, rows_ref.dtype)

    idx = idx_ref[...][:, :nnz]
    local = idx - lo
    inb = (local >= 0) & (local < part_rows) & (idx >= 0)
    _gather_pass(jnp.where(inb, local, -1), tbl_ref, rows_ref)


def embedding_bag(table, indices, *, partitions: int = 1, block_batch: int = 128,
                  interpret: Optional[bool] = None):
    """out[b] = sum_k table[indices[b, k]];  indices == -1 contribute zero.

    table: [vocab, dim] float; indices: int32[batch, nnz].  ``vocab`` need
    not divide ``partitions`` — the last partition is zero-padded inside the
    wrapper.
    """
    if interpret is None:
        interpret = default_interpret()
    vocab, dim = table.shape
    batch, nnz = indices.shape
    nnz_sub = lanes.sublane_pad(nnz)
    table, part, parts, dim_pad = _partitioned(table, partitions)
    idx, bb, bp, nnz_lane = _pad_batch(indices, block_batch)

    rows = pl.pallas_call(
        functools.partial(_gather_kernel, part_rows=part, nnz=nnz),
        grid=(bp // bb, parts),
        in_specs=[
            pl.BlockSpec((bb, nnz_lane), lambda b, p: (b, 0)),
            pl.BlockSpec((part, dim_pad), lambda b, p: (p, 0)),
        ],
        out_specs=pl.BlockSpec((bb, nnz_sub, dim_pad), lambda b, p: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, nnz_sub, dim_pad), table.dtype),
        interpret=interpret,
    )(idx, table)
    return _pool(rows, batch, nnz, dim)


def _cache_gather_kernel(slot_ref, cache_ref, rows_ref, *, cache_rows: int,
                         nnz: int):
    """Single pass over the (VMEM-resident) cache, the hot path: zero the
    rows tile, then fill hot entries from the cache."""
    rows_ref[...] = jnp.zeros(rows_ref.shape, rows_ref.dtype)
    slot = slot_ref[...][:, :nnz]
    inb = (slot >= 0) & (slot < cache_rows)
    _gather_pass(jnp.where(inb, slot, -1), cache_ref, rows_ref)


def _two_level_kernel(slot_ref, cold_ref, cache_ref, tbl_ref, rows_ref, *,
                      part_rows: int, cache_rows: int, nnz: int):
    """Grid dim 1: step 0 = cache pass, steps 1..P = table partition passes.

    Hot entries (slot >= 0) resolve from the cache and shadow any cold id;
    cold entries fall through the partitioned pass exactly like the uncached
    kernel.  Entries with neither contribute zero.
    """
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _cache():
        _cache_gather_kernel(slot_ref, cache_ref, rows_ref,
                             cache_rows=cache_rows, nnz=nnz)

    @pl.when(p > 0)
    def _table_pass():
        lo = (p - 1) * part_rows
        cold = cold_ref[...][:, :nnz]
        local = cold - lo
        # hot entries already resolved from the cache: slot wins over cold
        inb = ((local >= 0) & (local < part_rows) & (cold >= 0)
               & (slot_ref[...][:, :nnz] < 0))
        _gather_pass(jnp.where(inb, local, -1), tbl_ref, rows_ref)


def embedding_bag_cached(table, cache, slot_idx, cold_idx=None, *,
                         partitions: int = 1, block_batch: int = 128,
                         interpret: Optional[bool] = None):
    """Two-level cached embedding bag.

    out[b] = sum_k rows[b, k] with rows resolved per entry:

    - ``slot_idx[b, k] >= 0``: ``cache[slot_idx[b, k]]`` — ONE dense VMEM
      pass over the ``[cache_rows, dim]`` cache, no table traffic.
    - else ``cold_idx[b, k] >= 0``: ``table[cold_idx[b, k]]`` through the
      uncached kernel's partitioned pass.
    - both ``-1``: contributes zero (padding lanes).

    ``cold_idx=None`` asserts the lookahead plan staged every cold row into
    the cache (the fast path): the call lowers to the single cache pass and
    the table is never read.  When ``cache`` rows mirror the table rows the
    plan assigned them (the lookahead stage's invariant), the result is
    bit-identical to ``embedding_bag(table, original_indices)``.
    """
    if interpret is None:
        interpret = default_interpret()
    cache_rows, dim = cache.shape
    batch, nnz = slot_idx.shape
    nnz_sub = lanes.sublane_pad(nnz)
    dim_pad = lanes.lane_pad(dim)
    rows_pad = lanes.sublane_pad(cache_rows)
    cache = jnp.pad(cache, ((0, rows_pad - cache_rows), (0, dim_pad - dim)))
    slot, bb, bp, nnz_lane = _pad_batch(slot_idx, block_batch)

    if cold_idx is None:
        rows = pl.pallas_call(
            functools.partial(_cache_gather_kernel, cache_rows=cache_rows,
                              nnz=nnz),
            grid=(bp // bb,),
            in_specs=[
                pl.BlockSpec((bb, nnz_lane), lambda b: (b, 0)),
                pl.BlockSpec((rows_pad, dim_pad), lambda b: (0, 0)),
            ],
            out_specs=pl.BlockSpec((bb, nnz_sub, dim_pad), lambda b: (b, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((bp, nnz_sub, dim_pad), cache.dtype),
            interpret=interpret,
        )(slot, cache)
        return _pool(rows, batch, nnz, dim)

    table, part, parts, _ = _partitioned(table, partitions)
    cold, _, _, _ = _pad_batch(cold_idx, block_batch)
    rows = pl.pallas_call(
        functools.partial(_two_level_kernel, part_rows=part,
                          cache_rows=cache_rows, nnz=nnz),
        grid=(bp // bb, parts + 1),
        in_specs=[
            pl.BlockSpec((bb, nnz_lane), lambda b, p: (b, 0)),
            pl.BlockSpec((bb, nnz_lane), lambda b, p: (b, 0)),
            pl.BlockSpec((rows_pad, dim_pad), lambda b, p: (0, 0)),
            pl.BlockSpec((part, dim_pad),
                         lambda b, p: (jnp.maximum(p - 1, 0), 0)),
        ],
        out_specs=pl.BlockSpec((bb, nnz_sub, dim_pad), lambda b, p: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, nnz_sub, dim_pad), cache.dtype),
        interpret=interpret,
    )(slot, cold, cache, table)
    return _pool(rows, batch, nnz, dim)
