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
- ``lane_gather``: ``out[r, c] = table[idx[r, c]]`` against a row-layout
  table ref: per entry one dynamic-sublane row load and a one-hot lane
  reduce.  Cost is O(entries), independent of the table capacity.
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

# rows of an int32 tile staged into SMEM at a time by ``for_each_row``;
# bounds the SMEM scratch to SMEM_ROWS x lane_pad(width) words whatever
# the row tile
SMEM_ROWS = 64


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


def _lane_iota(width: int = LANE):
    return jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)


def lane_gather(tbl_ref, idx):
    """``out[r, c] = flat(tbl_ref)[idx[r, c]]`` for a row-layout table ref.

    ``idx``: int (rows, w), every entry in ``[0, capacity)``.  Each entry
    loads its table row with a dynamic sublane index and selects its lane
    with a one-hot reduce (exactly one non-zero term, so the sum is the
    entry bit for bit)."""
    rows, w = idx.shape
    wp = lane_pad(w)
    dtype = tbl_ref.dtype
    lane, col_ids = _lane_iota(), _lane_iota(wp)

    def scoped(got_ref, stage_ref, smem_ref):
        def row_fn(r, at):
            def col(c, out):
                v = at(c)
                row = tbl_ref[pl.ds(v >> LANE_BITS, 1), :]
                hit = jnp.sum(jnp.where(lane == (v & (LANE - 1)), row, 0),
                              axis=1, keepdims=True).astype(dtype)
                return jnp.where(col_ids == c, hit, out)

            got_ref[pl.ds(r, 1), :] = jax.lax.fori_loop(
                0, w, col, jnp.zeros((1, wp), dtype))

        for_each_row(idx, row_fn, stage_ref, smem_ref)
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
