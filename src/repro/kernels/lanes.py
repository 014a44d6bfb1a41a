"""Lane-alignment helpers shared by every Pallas kernel module.

Mosaic (TPU) tiles vectors as (8 sublanes x 128 lanes); memory blocks whose
minor dimension is not a multiple of 128 — or constructs like 1-D iota,
lane-collapsing reshapes, and flat dynamic gathers — do not lower, and its
vector gather (``take_along_axis``) only permutes within one vreg.  The
kernels therefore share one vocabulary of Mosaic-legal building blocks:

- ``lane_pad`` / ``sublane_pad``: round widths up to the hardware tile.
- **row layout**: a flat int32 table of ``capacity`` entries travels as a
  ``(table_rows(capacity), 128)`` array, entry ``v`` at ``[v >> 7, v & 127]``
  (``to_rows`` / ``from_rows``).  No sublane padding is wasted, and one
  entry is reachable with a dynamic *sublane* index, which Mosaic lowers.
- ``for_each_row``: visit an in-kernel int32 tile row by row with its
  entries as scalars.  The tile is staged VMEM -> SMEM in chunks of
  ``SMEM_ROWS`` rows (vector registers have no dynamic scalar extract;
  SMEM has dynamic scalar loads).
- ``for_each_block``: visit the same staged tile ``GATHER_ROWS`` rows at
  a time, every entry of a block a scalar at a static position.
- ``lane_gather``: ``out[r, c] = table[idx[r, c]]`` against a row-layout
  table ref, one block of ``GATHER_ROWS`` rows per loop step: per entry
  one dynamic-sublane row load and a lane rotate to its column, every
  lookup of a block independent of the others, one store per block.
  Cost is O(entries), independent of the table capacity: about 3.5
  bundles of loop body per entry, bound by the scalar address
  arithmetic, where one entry per step took 23 and stalled on each
  entry's dependent loads (on one TPU v5e, Pipeline II's grouped apply
  kernel, 1.7M entries per 65,536-row batch, went from 161 ms to 5.5 ms).
- ``update_entry``: read-modify-write one entry of a row-layout ref (the
  fit build's scatter-min / scatter-add, serialized like the paper's
  RAW-limited vocab build).
- ``onehot_lanes``: the in-kernel one-hot. The operator-level expression
  (``operators.OneHot.jnp_expr``) collapses the depth axis with a reshape
  that merges into the lane dimension — illegal under Mosaic — so the tile
  codegen emits this per-column concat form instead: same values, lane
  concatenation only, iota only in its 2-D broadcasted form.
- ``gather_scratch_bytes``: the planner's VMEM account of one in-kernel
  ``lane_gather`` / fit build (staging and gathered tiles).

Interpret mode evaluates the very same kernel bodies, so both modes compute
bit-identical values by construction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128      # minor-dim tile of a TPU vreg
SUBLANE = 8     # second-minor tile (float32/int32)
LANE_BITS = 7   # log2(LANE): row = v >> LANE_BITS, lane = v & (LANE - 1)

# rows of an int32 tile staged into SMEM at a time by ``for_each_row`` and
# ``for_each_block``; bounds the SMEM scratch to SMEM_ROWS x lane_pad(width)
# words whatever the row tile
SMEM_ROWS = 64
# rows of one ``lane_gather`` block: one sublane tile, stored at once
GATHER_ROWS = SUBLANE


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def lane_pad(w: int) -> int:
    """Pad a width up to the lane tile (>= 1 lane group)."""
    return round_up(max(int(w), 1), LANE)


def sublane_pad(w: int) -> int:
    """Pad a second-minor width up to the sublane tile."""
    return round_up(max(int(w), 1), SUBLANE)


# ---------------------------------------------------------------------------
# row layout of flat tables
# ---------------------------------------------------------------------------

def table_rows(capacity: int) -> int:
    """Rows of the (rows, LANE) layout holding ``capacity`` entries."""
    return sublane_pad(-(-int(capacity) // LANE))


def to_rows(flat):
    """Flat ``[capacity]`` (or ``(1, capacity)``) -> ``(table_rows, LANE)``;
    padding entries are zero and never addressed."""
    flat = flat.reshape(-1)
    n = table_rows(flat.shape[0]) * LANE
    return jnp.pad(flat, (0, n - flat.shape[0])).reshape(-1, LANE)


def from_rows(t, capacity: int):
    """``(rows, LANE)`` layout -> flat ``[capacity]``."""
    return t.reshape(-1)[:capacity]


# ---------------------------------------------------------------------------
# in-kernel scalar access
# ---------------------------------------------------------------------------

def scalar_scratch(rows: int, w: int) -> list:
    """Scratch ``for_each_row`` needs for a (rows, w) tile: a VMEM staging
    tile and an SMEM chunk.  Callers allocate it in their own
    ``pl.run_scoped`` (one scope per kernel region; interpret mode cannot
    discharge nested scopes that read SMEM in a loop)."""
    return [pltpu.VMEM((rows, lane_pad(w)), jnp.int32),
            pltpu.SMEM((min(SMEM_ROWS, rows), lane_pad(w)), jnp.int32)]


def for_each_row(vals, row_fn, stage_ref, smem_ref) -> None:
    """Call ``row_fn(r, at)`` for every row ``r`` of the in-kernel int32
    tile ``vals`` (rows, w), in row order; ``at(c)`` reads ``vals[r, c]``
    as a scalar (``c`` static or traced).

    The tile is stored to the VMEM ``stage_ref`` and copied to the SMEM
    ``smem_ref`` ``SMEM_ROWS`` rows at a time (``scalar_scratch``); a
    ``fori_loop`` walks each chunk's rows.  Callers walk a row's columns
    with a rolled ``fori_loop`` too, so a kernel's trace (and its
    interpret-mode compile) does not grow with the tile."""
    rows, w = vals.shape
    ch = smem_ref.shape[0]
    stage_ref[:, :w] = vals.astype(jnp.int32)
    for k0 in range(0, rows, ch):
        n = min(ch, rows - k0)
        pltpu.sync_copy(stage_ref.at[pl.ds(k0, n)], smem_ref.at[pl.ds(0, n)])

        def body(i, carry, k0=k0):
            row_fn(k0 + i, lambda c: smem_ref[i, c])
            return carry

        jax.lax.fori_loop(0, n, body, 0)


def for_each_block(vals, block_fn, stage_ref, smem_ref) -> None:
    """Call ``block_fn(r0, n, at)`` for every block of ``GATHER_ROWS`` rows
    of the in-kernel int32 tile ``vals`` (rows, w), in row order; ``n`` is
    the block's rows (``GATHER_ROWS``, or fewer in a tile's last block) and
    ``at(i, c)`` reads ``vals[r0 + i, c]`` as a scalar (``i``, ``c``
    static).

    The tile is staged as ``for_each_row`` stages it (``scalar_scratch``).
    One ``fori_loop`` walks the full blocks, so ``block_fn`` is traced
    once for them whatever the tile; a block that opens a chunk first
    copies the chunk's ``SMEM_ROWS`` rows to SMEM (the last chunk ends at
    the last full block, so every copy is whole).  A final partial block
    is staged and walked on its own."""
    rows, w = vals.shape
    ch = smem_ref.shape[0]
    stage_ref[:, :w] = vals.astype(jnp.int32)
    full, rem = divmod(rows, GATHER_ROWS)
    end = full * GATHER_ROWS
    one_chunk = rows <= ch
    if one_chunk:
        pltpu.sync_copy(stage_ref.at[pl.ds(0, rows)],
                        smem_ref.at[pl.ds(0, rows)])

    def body(b, carry):
        r0 = pl.multiple_of(b * GATHER_ROWS, GATHER_ROWS)
        if one_chunk:
            k0 = 0
        else:
            k0 = pl.multiple_of(jnp.minimum(r0 - r0 % ch, end - ch),
                                GATHER_ROWS)

            @pl.when(r0 % ch == 0)
            def _stage():
                pltpu.sync_copy(stage_ref.at[pl.ds(k0, ch)], smem_ref)

        i0 = r0 - k0
        block_fn(r0, GATHER_ROWS, lambda i, c: smem_ref[i0 + i, c])
        return carry

    if full:
        jax.lax.fori_loop(0, full, body, 0)
    if rem:
        i0 = end
        if not one_chunk:
            pltpu.sync_copy(stage_ref.at[pl.ds(end, rem)],
                            smem_ref.at[pl.ds(0, rem)])
            i0 = 0
        block_fn(end, rem, lambda i, c: smem_ref[i0 + i, c])


def _lane_iota():
    return jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)


def lane_gather(tbl_ref, idx):
    """``out[r, c] = flat(tbl_ref)[idx[r, c]]`` for a row-layout table ref.

    ``idx``: int (rows, w), every entry in ``[0, capacity)``.  The rows
    go ``GATHER_ROWS`` at a time (``for_each_block``) with the columns
    unrolled, so a loop step issues ``GATHER_ROWS * w`` lookups that do
    not depend on one another.  Each entry loads its table row with a
    dynamic sublane index and rotates it so that its lane lands on the
    entry's column, where a select keeps it (a pure data move, so the
    result is the entry bit for bit).  A block's rows are stored with
    one store."""
    rows, w = idx.shape
    wp = lane_pad(w)
    dtype = tbl_ref.dtype

    def gather_row(at, keep):
        groups = [jnp.zeros((1, LANE), dtype) for _ in range(wp // LANE)]
        for c in range(w):
            v = at(c)
            g, cl = divmod(c, LANE)
            row = tbl_ref[pl.ds(v >> LANE_BITS, 1), :]
            # a rotate by cl - v (mod LANE) moves lane v & (LANE - 1) to cl
            moved = pltpu.roll(row, (cl - v) & (LANE - 1), 1)
            groups[g] = jax.lax.select(keep[cl], moved, groups[g])
        return groups[0] if len(groups) == 1 else jnp.concatenate(groups, 1)

    def scoped(got_ref, stage_ref, smem_ref):
        def block_fn(r0, n, at):
            lane = _lane_iota()
            keep = [lane == cl for cl in range(min(w, LANE))]
            got = [gather_row(lambda c, i=i: at(i, c), keep) for i in range(n)]
            got_ref[pl.ds(r0, n), :] = (got[0] if n == 1
                                        else jnp.concatenate(got, 0))

        for_each_block(idx, block_fn, stage_ref, smem_ref)
        return got_ref[...][:, :w]

    return pl.run_scoped(scoped, pltpu.VMEM((rows, wp), dtype),
                         *scalar_scratch(rows, w))


def update_entry(ref, v, fn) -> None:
    """``flat(ref)[v] = fn(flat(ref)[v])`` for a row-layout ref (one
    dynamic-sublane row load, a lane-masked select, one row store)."""
    row_idx = pl.ds(v >> LANE_BITS, 1)
    row = ref[row_idx, :]
    ref[row_idx, :] = jnp.where(_lane_iota() == (v & (LANE - 1)),
                                fn(row), row)


def gather_scratch_bytes(block_rows: int, width: int) -> int:
    """VMEM bytes one in-kernel ``lane_gather`` holds on top of the tiles
    the working set already counts: the int32 staging tile of
    ``for_each_row`` and the gathered tile, both lane-padded (the SMEM
    chunk lives outside VMEM)."""
    return 2 * block_rows * lane_pad(width) * 4


def onehot_lanes(x, depth: int):
    """Lane-aligned one-hot of a 2-D int tile: (rows, w) -> (rows, w*depth).

    Column layout matches ``operators.OneHot`` exactly
    (``out[r, c*depth + j] = float(x[r, c] == j)``; out-of-range rows are
    all-zero), but the expansion is a lane concat of per-column indicator
    tiles instead of a trailing-axis reshape.
    """
    k = jax.lax.broadcasted_iota(jnp.int32, (1, depth), 1).astype(x.dtype)
    cols = [(x[:, c:c + 1] == k).astype(jnp.float32)
            for c in range(x.shape[1])]
    return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
