"""Vocabulary build (VocabGen) and lookup (VocabMap) Pallas kernels.

TPU adaptation of the paper's stateful operators (§3.2.2):

VocabGen — the FPGA builds the table in a pipelined RAW-serialized loop
(II = 2 cycles on-chip, ~6 off-chip).  On TPU the equivalent structure is a
table *partitioned across the grid* (the paper's "P HBM banks"): each grid
step owns one table partition in VMEM and scans the value stream, keeping the
min first-occurrence position for in-partition values.  The serial
read-modify-write over the stream inside a partition mirrors the paper's
RAW-limited II; partitions run in parallel exactly like HBM banks.

VocabMap — keyed lookups against the frozen table.  Partition-parallel form:
each grid step gathers hits for its table partition; a max-combine across
partitions assembles the result (every key hits exactly one partition, misses
contribute -1).  This avoids unsupported full-table dynamic gathers when the
table exceeds VMEM; the in-partition gather is ``kernels.lanes.lane_gather``.

Each partition travels in the row layout of ``kernels.lanes``
(``(table_rows(capacity // partitions), 128)``, rows stacked per partition);
the build reads the stream's values as scalars and updates one entry per
value with a row read-modify-write.  The wrappers re-interleave the logical
table on return, so any ``capacity % 128`` works in compiled mode.

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

ABSENT32 = 2 ** 31 - 1  # python int: safe to close over inside kernel bodies


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _partition_rows(flat, partitions: int, part: int):
    """Logical [partitions * part] table -> per-partition row layouts
    stacked along rows: (partitions * table_rows(part), 128)."""
    return jnp.concatenate(
        [lanes.to_rows(flat[i * part:(i + 1) * part])
         for i in range(partitions)], axis=0)


def _unpartition_rows(t, partitions: int, part: int):
    """Inverse of ``_partition_rows``: -> logical [partitions * part]."""
    return t.reshape(partitions, -1)[:, :part].reshape(-1)


# ---------------------------------------------------------------------------
# VocabGen: chunk-local first-occurrence build
# ---------------------------------------------------------------------------

def _build_kernel(vals_ref, fp_ref, *, part_size: int, width: int,
                  n_rows: int):
    """Grid dim 0 = table partition p, dim 1 = row tile of the stream.
    fp_ref: partition p of first_pos in the row layout."""
    p = pl.program_id(0)
    lo = p * part_size

    @pl.when(pl.program_id(1) == 0)
    def _init():
        fp_ref[...] = jnp.full(fp_ref.shape, ABSENT32, fp_ref.dtype)

    vals = vals_ref[...][:, :width]
    br = vals.shape[0]
    row0 = pl.program_id(1) * br

    def row_fn(r, at):
        gr = row0 + r

        def col(c, carry):
            v = at(c)
            local = v - lo
            ok = (gr < n_rows) & (v >= 0) & (local >= 0) & (local < part_size)
            pos = jnp.where(ok, gr * width + c, ABSENT32)
            lanes.update_entry(fp_ref, jnp.where(ok, local, 0),
                               lambda row: jnp.minimum(row, pos))
            return carry

        jax.lax.fori_loop(0, width, col, 0)

    pl.run_scoped(
        lambda stage_ref, smem_ref: lanes.for_each_row(
            vals, row_fn, stage_ref, smem_ref),
        *lanes.scalar_scratch(br, width))


def vocab_build_chunk(values, capacity: int, *, partitions: int = 1,
                      block_rows: int = 256,
                      interpret: Optional[bool] = None):
    """First-occurrence position within one chunk. int32[capacity], ABSENT32=absent.

    values: int32[n] or int32[rows, w] (positions are row-major flat
    offsets either way); entries outside [0, capacity) drop.
    """
    if interpret is None:
        interpret = default_interpret()
    if capacity % max(partitions, 1):
        raise ValueError("capacity must divide evenly into partitions")
    vals = values.reshape(values.shape[0], -1)
    rows, width = vals.shape
    part = capacity // partitions
    part_rows = lanes.table_rows(part)
    br = min(block_rows, _round_up(max(rows, 1), 8))
    rp = _round_up(max(rows, 1), br)
    wp = lanes.lane_pad(width)
    vp = jnp.pad(vals, ((0, rp - rows), (0, wp - width)), constant_values=-1)

    out = pl.pallas_call(
        functools.partial(_build_kernel, part_size=part, width=width,
                          n_rows=rows),
        name="vocab_build",
        grid=(partitions, rp // br),
        in_specs=[pl.BlockSpec((br, wp), lambda p, r: (r, 0))],
        out_specs=pl.BlockSpec((part_rows, lanes.LANE), lambda p, r: (p, 0)),
        out_shape=jax.ShapeDtypeStruct((partitions * part_rows, lanes.LANE),
                                       jnp.int32),
        interpret=interpret,
    )(vp)
    return _unpartition_rows(out, partitions, part)


# ---------------------------------------------------------------------------
# VocabMap: partition-parallel gather
# ---------------------------------------------------------------------------

def _lookup_kernel(x_ref, tbl_ref, o_ref, *, part_size: int, cols: int):
    """Grid: (row blocks, partitions). o accumulates max over partitions."""
    p = pl.program_id(1)
    lo = p * part_size
    x = x_ref[...][:, :cols]

    @pl.when(p == 0)
    def _init():
        o_ref[...] = jnp.full(o_ref.shape, -1, o_ref.dtype)

    local = x - lo
    inb = (local >= 0) & (local < part_size)
    got = lanes.lane_gather(tbl_ref, jnp.where(inb, local, 0))
    got = jnp.where(inb, got, -1)
    o_ref[:, :cols] = jnp.maximum(o_ref[:, :cols], got)


def vocab_lookup(x, table, n_unique, *, partitions: int = 1,
                 block_rows: int = 256, interpret: Optional[bool] = None):
    """Map x through table (absent -> -1 -> OOV index n_unique).

    x: int32[rows, cols] in [0, capacity); table: int32[capacity].
    """
    if interpret is None:
        interpret = default_interpret()
    rows, cols = x.shape
    capacity = int(table.shape[0])
    if capacity % max(partitions, 1):
        raise ValueError("capacity must divide evenly into partitions")
    part = capacity // partitions
    part_rows = lanes.table_rows(part)
    br = min(block_rows, _round_up(rows, 8))
    bc = lanes.lane_pad(cols)
    rp = _round_up(rows, br)
    xp = jnp.pad(x, ((0, rp - rows), (0, bc - cols)))

    out = pl.pallas_call(
        functools.partial(_lookup_kernel, part_size=part, cols=cols),
        name="vocab_lookup",
        grid=(rp // br, partitions),
        in_specs=[
            pl.BlockSpec((br, bc), lambda r, p: (r, 0)),
            pl.BlockSpec((part_rows, lanes.LANE), lambda r, p: (p, 0)),
        ],
        out_specs=pl.BlockSpec((br, bc), lambda r, p: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, bc), jnp.int32),
        interpret=interpret,
    )(xp, _partition_rows(table, partitions, part))
    out = out[:rows, :cols]
    return jnp.where(out >= 0, out, n_unique).astype(jnp.int32)
