"""Streaming Pallas dataflow kernels (paper §3: the full FPGA pipeline).

This module is the kernel-side half of plan-level fusion.  It hosts the
factories, in increasing order of fusion:

``make_fused_stage``
    One chain of stateless operators as one streaming kernel (Stage-A).
    Used by the stage-at-a-time fallback path.

``make_packer``
    The format-aware packer as its own kernel (fallback epilogue): column
    blocks are concatenated along lanes, cast to the trainer dtype, and the
    width padded to the layout ``train_step`` declares.

``make_output_dataflow``
    The whole backward slice of one ``PackOutput`` as ONE row-tiled kernel —
    the TPU statement of the paper's streaming dataflow.  Per grid step, a
    row block of every raw source streams into VMEM, the fused elementwise
    chains / hex decode / vocab rank-lookup / one-hot expansion execute
    per-tile as ``TileStep``s of a single kernel body, and every terminal
    buffer is stored at its static lane offset of the packed output block.
    Intermediates live only in VMEM registers — no HBM tensor ever
    materializes between operators, and the separate packer pass disappears
    (packing is the kernel's epilogue).  Each byte of the stream crosses
    HBM exactly twice: raw in, packed out.

``make_group_dataflow``
    The merged backward slice of SEVERAL ``PackOutput``s (a planner
    ``DataflowGroup``) as ONE row-tiled kernel with one packed output block
    per member.  The shared ``TileStep`` program runs once per tile; each
    member's packer epilogue reads its terminals from the same VMEM tile
    environment — the optimizer's cross-output CSE, realized in-kernel.

``make_fit_dataflow``
    The fit-phase sibling: the backward slice of one ``VocabFit`` — decode,
    bounding chains, joins — plus the chunk first-occurrence + count build
    as ONE row-tiled kernel.  The two int32 accumulators are the kernel
    outputs, held whole in VMEM in the row layout across the grid.  The
    build is the paper's RAW-serialized loop: every value of a tile is
    read as a scalar and folded into its accumulator entries by a row
    read-modify-write (``kernels.lanes``), in compiled and interpret mode
    alike.

Vocabulary tables enter the dataflow kernel pre-resolved: the compiler folds
the OOV rule (``miss -> n_unique``) into the table before the call, so the
in-kernel lookup is a pure gather (``kernels.lanes.lane_gather``) from a
table resident whole in VMEM in the row layout (``(capacity/128, 128)``).
The gather walks a tile ``lanes.GATHER_ROWS`` rows per loop step with the
lookup's columns unrolled: every lookup of a step is independent of the
others, and the step stores its rows at once.  Its cost is O(entries),
the same for every caller whatever the table's capacity.

Tiling: every streamed block is lane-aligned — source and packed output
blocks are padded up to multiples of 128 lanes host-side (padding lanes
carry zeros and are sliced off in-kernel / on return), block rows are
multiples of 8 sublanes, and the grid streams row blocks — the paper's
batch-of-rows FIFO granularity, in the shape Mosaic actually tiles.

``interpret=None`` on every factory resolves through
``kernels.backend.default_interpret`` (compiled on a TPU, interpret
otherwise); passing an explicit bool pins the mode.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import lanes
from repro.kernels.backend import default_interpret


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _resolve_interpret(interpret) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def _compiler_params(vmem_limit_bytes: Optional[int]):
    """Mosaic's scoped-VMEM limit for a fused kernel (None: its default)."""
    if vmem_limit_bytes is None:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=int(vmem_limit_bytes))


# ---------------------------------------------------------------------------
# Stage-A: one fused stateless chain as one kernel (fallback path)
# ---------------------------------------------------------------------------

def make_fused_stage(chain_fn, *, in_dtype, out_dtype, hex_width: int = 0,
                     block_rows: int = 256, block_cols: int = 512,
                     interpret: Optional[bool] = None):
    """Build a jit-compatible fn: x -> fused(x).

    chain_fn: elementwise block function. For hex inputs it receives the
    (w, br, bc) uint8 block and must fold the leading digit axis itself.
    """
    interpret = _resolve_interpret(interpret)

    def kernel(x_ref, o_ref):
        o_ref[...] = chain_fn(x_ref[...]).astype(o_ref.dtype)

    @functools.partial(jax.jit, static_argnames=())
    def run(x):
        if hex_width:
            w, rows, cols = x.shape
            assert w == hex_width, (x.shape, hex_width)
        else:
            rows, cols = x.shape
        br = min(block_rows, _round_up(rows, 8))
        bc = min(_round_up(block_cols, 128), lanes.lane_pad(cols))
        rp, cp = _round_up(rows, br), _round_up(cols, bc)
        # pad to block multiples (padding lanes carry zeros; sliced off below)
        if hex_width:
            xp = jnp.pad(x, ((0, 0), (0, rp - rows), (0, cp - cols)))
            in_spec = pl.BlockSpec((hex_width, br, bc), lambda i, j: (0, i, j))
        else:
            xp = jnp.pad(x, ((0, rp - rows), (0, cp - cols)))
            in_spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
        grid = (rp // br, cp // bc)
        out = pl.pallas_call(
            kernel,
            name="etl_fused_stage",
            grid=grid,
            in_specs=[in_spec],
            out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((rp, cp), out_dtype),
            interpret=interpret,
        )(xp)
        return out[:rows, :cols]

    return run


def vmem_bytes_estimate(in_dtype, out_dtype, hex_width: int,
                        block_rows: int, block_cols: int) -> int:
    """Planner helper: VMEM working set claimed by one grid step."""
    in_b = np.dtype(in_dtype).itemsize * block_rows * block_cols * (hex_width or 1)
    out_b = np.dtype(out_dtype).itemsize * block_rows * block_cols
    return 2 * (in_b + out_b)  # x2 for double buffering


# ---------------------------------------------------------------------------
# Format-aware packer as its own kernel (fallback epilogue)
# ---------------------------------------------------------------------------

def make_packer(col_widths, in_dtypes, out_dtype, *, pad_cols_to: int = 128,
                block_rows: int = 256, interpret: Optional[bool] = None):
    """Build fn(blocks...) -> packed [rows, padded(sum(col_widths))].

    Column blocks and the packed block are lane-padded to 128-multiples for
    the kernel; the logical ``pad_cols_to`` layout width is sliced back out
    on return.
    """
    interpret = _resolve_interpret(interpret)
    col_widths = [int(w) for w in col_widths]
    total = sum(col_widths)
    padded = _round_up(total, pad_cols_to)
    lane_padded = lanes.lane_pad(padded)
    lane_widths = [lanes.lane_pad(w) for w in col_widths]
    offsets = np.cumsum([0] + col_widths).tolist()

    def kernel(*refs):
        o_ref = refs[-1]
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
        for k, x_ref in enumerate(refs[:-1]):
            x = x_ref[...][:, :col_widths[k]]
            o_ref[:, offsets[k]:offsets[k + 1]] = x.astype(o_ref.dtype)

    def run(*blocks):
        assert len(blocks) == len(col_widths)
        rows = blocks[0].shape[0]
        br = min(block_rows, _round_up(rows, 8))
        rp = _round_up(rows, br)
        padded_blocks = [
            jnp.pad(b, ((0, rp - rows), (0, lw - b.shape[1])))
            for b, lw in zip(blocks, lane_widths)]
        out = pl.pallas_call(
            kernel,
            name="etl_pack",
            grid=(rp // br,),
            in_specs=[pl.BlockSpec((br, lw), lambda r: (r, 0))
                      for lw in lane_widths],
            out_specs=pl.BlockSpec((br, lane_padded), lambda r: (r, 0)),
            out_shape=jax.ShapeDtypeStruct((rp, lane_padded), out_dtype),
            interpret=interpret,
        )(*padded_blocks)
        return out[:rows, :padded]

    return run


# ---------------------------------------------------------------------------
# The fused per-output streaming dataflow kernel
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StreamInput:
    """One raw column block streamed through the kernel, row-tiled."""

    name: str
    width: int
    dtype: np.dtype
    hex_width: int = 0  # > 0: digit-major uint8[hex_width, rows, width]


@dataclasses.dataclass(frozen=True)
class TableInput:
    """One frozen, OOV-resolved vocab table staged whole per grid step."""

    vocab_id: str
    capacity: int


@dataclasses.dataclass(frozen=True)
class TileStep:
    """One operator application inside the kernel body.

    kind:
      "map"    — unary per-tile fn (fused elementwise chain, hex fold,
                 one-hot expansion); ``fn(tile) -> tile``.
      "join"   — binary per-tile fn (Cartesian cross); ``fn(a, b) -> tile``.
      "lookup" — gather through ``tables[table]`` (rank lookup; the OOV rule
                 is pre-folded into the table, so a miss gathers n_unique).
    """

    kind: str
    out: str
    args: tuple
    fn: Optional[Callable] = None
    table: int = -1


def _row_tile_sources(inputs, srcs, br: int, rp: int):
    """Pad each raw source to the row-tile multiple and a lane-multiple
    width, and emit its BlockSpec (hex sources are digit-major 3-d; the
    digit axis is not tiled).  The kernel slices each tile back to its
    natural width, so padding lanes never enter the step program."""
    rows = srcs[0].shape[1] if inputs[0].hex_width else srcs[0].shape[0]
    padded_srcs, in_specs = [], []
    for inp, x in zip(inputs, srcs):
        wp = lanes.lane_pad(inp.width)
        if inp.hex_width:
            padded_srcs.append(
                jnp.pad(x, ((0, 0), (0, rp - rows), (0, wp - inp.width))))
            in_specs.append(pl.BlockSpec((inp.hex_width, br, wp),
                                         lambda r: (0, r, 0)))
        else:
            padded_srcs.append(
                jnp.pad(x, ((0, rp - rows), (0, wp - inp.width))))
            in_specs.append(pl.BlockSpec((br, wp), lambda r: (r, 0)))
    return padded_srcs, in_specs


def _load_source_env(inputs, src_refs) -> dict:
    """Read each lane-padded source tile and slice to its natural width."""
    env = {}
    for inp, r in zip(inputs, src_refs):
        env[inp.name] = r[...][..., :inp.width]
    return env


def _table_layout(tables, tbls):
    """Each ``(1, capacity)`` resolved table in the row layout
    (``lanes.to_rows``), resident whole in VMEM for the entire grid: one
    copy in, no per-step re-fetch, no second pipeline buffer."""
    laid = []
    for t, a in zip(tables, tbls):
        assert a.shape == (1, t.capacity), (a.shape, t.capacity)
        laid.append(lanes.to_rows(a))
    return laid, [pl.BlockSpec(memory_space=pltpu.VMEM) for _ in tables]


def _run_tile_steps(env: dict, steps, tbl_refs, capacities):
    """Execute the TileStep program over VMEM-resident tiles in ``env``."""
    for st in steps:
        if st.kind == "map":
            env[st.out] = st.fn(env[st.args[0]])
        elif st.kind == "join":
            env[st.out] = st.fn(env[st.args[0]], env[st.args[1]])
        elif st.kind == "lookup":
            # row-layout, OOV-resolved table ref
            safe = jnp.clip(env[st.args[0]], 0, capacities[st.table] - 1)
            env[st.out] = lanes.lane_gather(tbl_refs[st.table], safe)
        else:
            raise NotImplementedError(st.kind)


def make_output_dataflow(inputs: Sequence[StreamInput],
                         tables: Sequence[TableInput],
                         steps: Sequence[TileStep],
                         terminals: Sequence[tuple],
                         out_dtype, *, pad_cols_to: int = 1,
                         block_rows: int = 256,
                         interpret: Optional[bool] = None,
                         vmem_limit_bytes: Optional[int] = None):
    """Build fn(*sources, *tables) -> packed [rows, padded(sum widths)].

    ``terminals`` is the ordered list of ``(buffer_name, width)`` pairs the
    packer epilogue writes; names refer to stream inputs or step outputs.
    The returned callable issues exactly ONE ``pallas_call``.
    """
    interpret = _resolve_interpret(interpret)
    inputs = list(inputs)
    tables = list(tables)
    steps = list(steps)
    terminals = [(str(n), int(w)) for n, w in terminals]
    total = sum(w for _, w in terminals)
    padded = _round_up(max(total, 1), max(pad_cols_to, 1))
    lane_padded = lanes.lane_pad(padded)
    offsets = np.cumsum([0] + [w for _, w in terminals]).tolist()
    capacities = [t.capacity for t in tables]
    n_src = len(inputs)

    def kernel(*refs):
        src_refs, tbl_refs, o_ref = refs[:n_src], refs[n_src:-1], refs[-1]
        env = _load_source_env(inputs, src_refs)
        _run_tile_steps(env, steps, tbl_refs, capacities)
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
        for (name, w), off in zip(terminals, offsets):
            o_ref[:, off:off + w] = env[name].astype(o_ref.dtype)

    def run(*arrays):
        assert len(arrays) == n_src + len(tables), (len(arrays), n_src)
        srcs, tbls = arrays[:n_src], arrays[n_src:]
        rows = srcs[0].shape[1] if inputs[0].hex_width else srcs[0].shape[0]
        br = min(block_rows, _round_up(rows, 8))
        rp = _round_up(rows, br)
        padded_srcs, in_specs = _row_tile_sources(inputs, srcs, br, rp)
        padded_tbls, tbl_specs = _table_layout(tables, tbls)
        out = pl.pallas_call(
            kernel,
            name="etl_apply_output",
            grid=(rp // br,),
            in_specs=in_specs + tbl_specs,
            out_specs=pl.BlockSpec((br, lane_padded), lambda r: (r, 0)),
            out_shape=jax.ShapeDtypeStruct((rp, lane_padded), out_dtype),
            interpret=interpret,
            compiler_params=_compiler_params(vmem_limit_bytes),
        )(*padded_srcs, *padded_tbls)
        return out[:rows, :padded]

    return run


# ---------------------------------------------------------------------------
# The multi-output fused streaming dataflow kernel (DataflowGroup lowering)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GroupOutput:
    """The packer epilogue of one member of a ``DataflowGroup``."""

    name: str
    terminals: tuple  # ((buffer_name, width), ...) in pack order
    out_dtype: np.dtype
    pad_cols_to: int = 1


def make_group_dataflow(inputs: Sequence[StreamInput],
                        tables: Sequence[TableInput],
                        steps: Sequence[TileStep],
                        outputs: Sequence[GroupOutput], *,
                        block_rows: int = 256,
                        interpret: Optional[bool] = None,
                        vmem_limit_bytes: Optional[int] = None):
    """Build fn(*sources, *tables) -> tuple of packed arrays, one per output.

    The grouped form of ``make_output_dataflow``: the merged backward slice
    of SEVERAL ``PackOutput``s runs as ONE row-tiled ``pallas_call``.  Per
    grid step the shared ``TileStep`` program executes exactly once over the
    union tile environment, then each member output's packer epilogue reads
    its terminals from that one environment and stores them at static lane
    offsets of its own packed block — stages shared across outputs are
    computed once per tile instead of once per output.
    """
    interpret = _resolve_interpret(interpret)
    inputs = list(inputs)
    tables = list(tables)
    steps = list(steps)
    outputs = list(outputs)
    capacities = [t.capacity for t in tables]
    n_src = len(inputs)
    n_out = len(outputs)
    paddeds, lane_paddeds, offsets_per_out = [], [], []
    for g in outputs:
        widths = [int(w) for _, w in g.terminals]
        padded = _round_up(max(sum(widths), 1), max(g.pad_cols_to, 1))
        paddeds.append(padded)
        lane_paddeds.append(lanes.lane_pad(padded))
        offsets_per_out.append(np.cumsum([0] + widths).tolist())

    def kernel(*refs):
        src_refs = refs[:n_src]
        tbl_refs = refs[n_src:-n_out]
        out_refs = refs[-n_out:]
        env = _load_source_env(inputs, src_refs)
        _run_tile_steps(env, steps, tbl_refs, capacities)
        for g, o_ref, offs in zip(outputs, out_refs, offsets_per_out):
            o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
            for (name, w), off in zip(g.terminals, offs):
                o_ref[:, off:off + w] = env[name].astype(o_ref.dtype)

    def run(*arrays):
        assert len(arrays) == n_src + len(tables), (len(arrays), n_src)
        srcs, tbls = arrays[:n_src], arrays[n_src:]
        rows = srcs[0].shape[1] if inputs[0].hex_width else srcs[0].shape[0]
        br = min(block_rows, _round_up(rows, 8))
        rp = _round_up(rows, br)
        padded_srcs, in_specs = _row_tile_sources(inputs, srcs, br, rp)
        padded_tbls, tbl_specs = _table_layout(tables, tbls)
        outs = pl.pallas_call(
            kernel,
            name="etl_apply_group",
            grid=(rp // br,),
            in_specs=in_specs + tbl_specs,
            out_specs=[pl.BlockSpec((br, lp), lambda r: (r, 0))
                       for lp in lane_paddeds],
            out_shape=[jax.ShapeDtypeStruct((rp, lp), g.out_dtype)
                       for g, lp in zip(outputs, lane_paddeds)],
            interpret=interpret,
            compiler_params=_compiler_params(vmem_limit_bytes),
        )(*padded_srcs, *padded_tbls)
        return tuple(o[:rows, :p] for o, p in zip(outs, paddeds))

    return run


# ---------------------------------------------------------------------------
# The fused per-vocab streaming *fit* kernel
# ---------------------------------------------------------------------------

ABSENT32 = 2 ** 31 - 1  # matches kernels.vocab / kernels.ref chunk sentinel


def make_fit_dataflow(inputs: Sequence[StreamInput],
                      steps: Sequence[TileStep],
                      value_buf: str, capacity: int, *,
                      block_rows: int = 256,
                      interpret: Optional[bool] = None,
                      vmem_limit_bytes: Optional[int] = None):
    """Build fn(*sources) -> (first_pos int32[capacity], counts int32[capacity]).

    One ``pallas_call`` over the row tiles: row tiles of every raw source
    stream through the ``TileStep`` chain (map/join only — lookups cannot
    precede a fit), and the chunk first-occurrence positions and
    occurrence counts accumulate into two row-layout tables
    (``lanes.to_rows``) resident whole in VMEM across the grid.
    Semantics match the staged path exactly: positions are global
    row-major flat offsets over the unpadded chunk, ``ABSENT32`` marks
    values absent from the chunk, counts sum every occurrence (the
    frequency-filter input), and negative / out-of-capacity values drop.

    The update is the paper's RAW-serialized build: each value of the
    tile is read as a scalar (``lanes.for_each_row``) and folded into its
    accumulator entries with one row read-modify-write each
    (``lanes.update_entry``).  A dropped value rewrites its row unchanged,
    so the loop has no data-dependent branch.  Interpret mode runs the
    same body, so the modes agree bit for bit.
    """
    inputs = list(inputs)
    steps = list(steps)
    interpret = _resolve_interpret(interpret)
    n_src = len(inputs)
    acc_rows = lanes.table_rows(capacity)

    def kernel(*refs, n_rows: int):
        src_refs, fp_ref, cnt_ref = refs[:n_src], refs[-2], refs[-1]

        @pl.when(pl.program_id(0) == 0)
        def _init():
            fp_ref[...] = jnp.full(fp_ref.shape, ABSENT32, fp_ref.dtype)
            cnt_ref[...] = jnp.zeros(cnt_ref.shape, cnt_ref.dtype)

        env = _load_source_env(inputs, src_refs)
        for st in steps:
            if st.kind == "map":
                env[st.out] = st.fn(env[st.args[0]])
            elif st.kind == "join":
                env[st.out] = st.fn(env[st.args[0]], env[st.args[1]])
            else:  # pragma: no cover - legality pass rejects lookups
                raise NotImplementedError(st.kind)
        vals = env[value_buf]
        br, width = vals.shape
        row0 = pl.program_id(0) * br

        def row_fn(r, at):
            gr = row0 + r

            def col(c, carry):
                v = at(c)
                ok = (gr < n_rows) & (v >= 0) & (v < capacity)
                slot = jnp.where(ok, v, 0)
                pos = jnp.where(ok, gr * width + c, ABSENT32)
                lanes.update_entry(fp_ref, slot,
                                   lambda row: jnp.minimum(row, pos))
                lanes.update_entry(cnt_ref, slot,
                                   lambda row: row + ok.astype(row.dtype))
                return carry

            jax.lax.fori_loop(0, width, col, 0)

        pl.run_scoped(
            lambda stage_ref, smem_ref: lanes.for_each_row(
                vals, row_fn, stage_ref, smem_ref),
            *lanes.scalar_scratch(br, width))

    def run(*srcs):
        assert len(srcs) == n_src, (len(srcs), n_src)
        rows = srcs[0].shape[1] if inputs[0].hex_width else srcs[0].shape[0]
        br = min(block_rows, _round_up(rows, 8))
        rp = _round_up(rows, br)
        padded_srcs, in_specs = _row_tile_sources(inputs, srcs, br, rp)
        acc = jax.ShapeDtypeStruct((acc_rows, lanes.LANE), jnp.int32)
        fp, cnt = pl.pallas_call(
            functools.partial(kernel, n_rows=rows),
            name="etl_fit_build",
            grid=(rp // br,),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 2,
            out_shape=[acc, acc],
            interpret=interpret,
            compiler_params=_compiler_params(vmem_limit_bytes),
        )(*padded_srcs)
        return lanes.from_rows(fp, capacity), lanes.from_rows(cnt, capacity)

    return run
