"""Compiler: lowers an ExecutionPlan to an executable pipeline (paper §3.1/§3.4).

Three backends share identical semantics (tests enforce bit-equality):

- ``numpy``  : the CPU-baseline oracle (the paper's pandas path).
- ``jnp``    : XLA-jitted; stages are fused by XLA (the GPU/NVTabular analogue).
- ``pallas`` : the streaming-dataflow analogue of the paper's FPGA pipeline.

Plans are rewritten by ``core/optimizer.optimize_plan`` before lowering
(``optimize="auto"``, the default): cross-output CSE, dead-stage pushdown,
and ``DataflowGroup`` formation.  The rewrite applies to every backend, so
the three-backend bit-equality invariant also pins optimized semantics;
``optimize="off"`` compiles the planner's plan verbatim.

The pallas backend then has three lowerings, chosen per ``PackOutput`` —
the fallback ladder is grouped → fused → staged:

- **grouped** (``fuse="auto"`` + ``optimize="auto"``): every
  ``DataflowGroup`` the optimizer proved legal lowers to ONE row-tiled
  streaming kernel emitting ALL member outputs' packed blocks per tile
  (``kernels/dataflow.make_group_dataflow``); stages shared across member
  outputs execute once per tile instead of once per output.
- **fused** (``fuse="auto"``): every legal ungrouped output lowers to ONE
  row-tiled streaming kernel (``kernels/dataflow.make_output_dataflow``).
  Raw column blocks stream through VMEM; the fused elementwise chains, hex
  decode, vocab rank-lookup and one-hot expansion execute per-tile as stages
  of a single kernel body; results land at their static lane offsets of the
  packed output.  No intermediate HBM tensors, no separate packer pass —
  this is the paper's "operators connected by on-chip FIFOs with a
  format-aware packer" as one ``pallas_call`` per output.
- **staged** (fallback, or ``fuse="off"``): each fused stage / vocab op /
  packer runs as its own Pallas kernel with full HBM materialization in
  between — the NVTabular-style baseline the paper argues against, kept both
  as the legality escape hatch (HBM-resident tables, oversized tiles,
  unknown stage kinds) and as the measurable comparison point for
  ``benchmarks/bench_pipelines.py``.

Either way the whole apply program is wrapped in one jit so a batch is a
single device dispatch, and the numpy/jnp oracles are untouched — the
three-backend bit-equality invariant pins fused and staged semantics alike.

The pallas kernels run interpret (CPU validation) or compiled
(Mosaic, on a TPU) per the ONE flag resolved here: ``interpret=None`` asks
``kernels.backend.default_interpret``, the resolved
bool re-judges fusion legality for the compiled lowering's VMEM extra
(``reason_kind="mosaic-illegal"`` fallback, never a crash) and is handed
to every kernel — kernels never re-resolve it.

Vocabulary *fit* is streamed: chunked first-occurrence build, merged into a
two-int32 global state, finalized into frozen rank tables.  On the pallas
backend the fit chunk has the same two lowerings as apply, chosen per
``VocabFit`` from the plan's ``FitProgram`` nodes:

- **fused** (``fuse="auto"``): every legal vocab lowers its whole fit chunk
  — upstream chains, hex decode, and the first-occurrence + count build — to
  ONE row-tiled streaming kernel (``kernels/dataflow.make_fit_dataflow``);
  no intermediate HBM tensors between the upstream stages and the build.
- **staged** (fallback, or ``fuse="off"``): upstream stages run as separate
  kernels with HBM materialization, then ``kernels/vocab.vocab_build_chunk``
  builds the first-pos table (HBM-placed capacities always take this path —
  the fused kernel's accumulators are VMEM-resident).

Chunk results are merged identically either way, so ``PipelineState`` is
bit-identical across lowerings (tests pin this).  Tables are pipeline state,
versioned for point-in-time correctness, and passed to the apply program as
arguments (no recompilation on table refresh — the partial-reconfiguration
analogue is a state swap).  For fused outputs the OOV rule is folded into the
table once per table version (cached host-side; O(capacity) at fit/swap time,
nothing per batch), so the in-kernel lookup is a pure gather.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import operators as ops_lib
from repro.core.dag import NodeType
from repro.core.optimizer import optimize_plan
from repro.core.planner import (CrossStage, DataflowGroup, DataflowProgram,
                                ExecutionPlan, FitProgram, FusedStage,
                                OneHotStage, PackOutput, VocabLookupStage,
                                build_plan_programs, kernel_vmem_limit)
from repro.kernels import lanes
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.dataflow import (GroupOutput, StreamInput, TableInput,
                                    TileStep)


def count_pallas_calls(jaxpr) -> int:
    """Count ``pallas_call`` equations in a (Closed)Jaxpr, nested included.

    Used by tests to assert the fused lowering really issues a single
    streaming kernel per PackOutput.
    """
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    n = 0
    for eqn in inner.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    n += count_pallas_calls(sub)
    return n


@dataclasses.dataclass
class PipelineState:
    """Frozen vocabulary tables + version (freshness bookkeeping)."""

    tables: dict  # vocab_id -> int32[capacity]
    n_unique: dict  # vocab_id -> int (python int; also passed as scalar array)
    version: int = 0

    def as_args(self):
        keys = sorted(self.tables)
        return ([self.tables[k] for k in keys],
                [jnp.asarray(self.n_unique[k], jnp.int32) for k in keys], keys)


def _chain_fn(stage: FusedStage):
    """Code-generate the fused elementwise function for one stage."""
    ops_seq = list(stage.ops)
    hexw = stage.in_hex_width

    def chain(x):
        rest = ops_seq
        if hexw:
            if not isinstance(ops_seq[0], ops_lib.Hex2Int):
                raise TypeError("hex source must be consumed by Hex2Int first")
            x = kref.hex2int_digit_major(x)
            rest = ops_seq[1:]
        for op in rest:
            x = op.jnp_expr(x)
        return x

    return chain


def _chain_numpy(stage: FusedStage, x):
    ops_seq = list(stage.ops)
    if stage.in_hex_width:
        if not isinstance(ops_seq[0], ops_lib.Hex2Int):
            raise TypeError("hex source must be consumed by Hex2Int first")
        # numpy path uses trailing-hex layout [rows, cols, w]
        x = ops_seq[0].numpy(x)
        ops_seq = ops_seq[1:]
    for op in ops_seq:
        x = op.numpy(x)
    return x


class CompiledPipeline:
    """Executable ETL pipeline with fit/apply phases."""

    def __init__(self, plan: ExecutionPlan, graph, backend: str = "jnp", *,
                 interpret: Optional[bool] = None, name: str = "pipeline",
                 fuse: str = "auto", optimize: str = "auto", semantics=None):
        if backend not in ("numpy", "jnp", "pallas"):
            raise ValueError(f"unknown backend {backend!r}")
        # fuse: "auto" / "off", or a per-output spec — a set/sequence of
        # output names to force STAGED (the controller's per-output fuse
        # knob), or a {output: bool} dict (False = staged)
        fuse_off: frozenset = frozenset()
        if isinstance(fuse, dict):
            fuse_off = frozenset(k for k, v in fuse.items() if not v)
            fuse = "auto"
        elif isinstance(fuse, (set, frozenset, list, tuple)):
            fuse_off = frozenset(fuse)
            fuse = "auto"
        elif fuse not in ("auto", "off"):
            raise ValueError(f"unknown fuse mode {fuse!r}")
        self._fuse_off = fuse_off
        if optimize not in ("auto", "off"):
            raise ValueError(f"unknown optimize mode {optimize!r}")
        # resolve the ONE interpret flag first: fusion legality depends on it
        # (the compiled lowering's lane-padding / gather scratch shrinks what
        # fits the VMEM budget), so it must be settled before any legality
        # rebuild below — kernels never re-resolve, they are handed this flag
        self.interpret = (kops.default_interpret() if interpret is None
                          else bool(interpret))
        if backend == "pallas" and not self.interpret and not plan.compiled_mode:
            # re-judge every fusion slice for the compiled lowering; slices
            # legal in interpret mode but over the compiled budget fall back
            # staged with reason_kind "mosaic-illegal" (never a crash)
            plan = dataclasses.replace(
                plan, dataflows=[], fit_dataflows=[], groups=[],
                opt_info=dict(plan.opt_info))
            build_plan_programs(plan, compiled=True)
        if optimize == "auto":
            # plan-level rewrite (CSE + pushdown + grouping); applied for
            # every backend so numpy/jnp/pallas stay bit-identical over the
            # SAME rewritten plan — the optimizer equivalence property then
            # pins optimize="auto" against "off" across backends.  The
            # rewrite preserves plan.compiled_mode, so regrouping keeps
            # judging merged slices with the mode resolved above.
            plan = optimize_plan(plan)
        self.plan = plan
        self.graph = graph
        self.backend = backend
        self.name = name
        self.fuse = fuse
        self.optimize = optimize
        # the template's PipelineSemantics ride along so the runtime (and
        # EtlJob) see the declared freshness/ordering/batching contract
        self.semantics = semantics
        # per-output fused programs: only the pallas backend has a tile
        # codegen; jnp relies on XLA fusion and numpy is the oracle
        self._fused_programs: dict[str, DataflowProgram] = {}
        self._fused_fit_programs: dict[str, FitProgram] = {}
        # multi-output fused dataflows: groups the optimizer proved legal,
        # active only where the fused tile codegen is (pallas + fuse=auto)
        self._active_groups: list[DataflowGroup] = []
        self._grouped_outputs: dict[str, int] = {}
        if backend == "pallas" and fuse == "auto":
            self._fused_programs = {dp.output: dp for dp in plan.dataflows
                                    if dp.legal
                                    and dp.output not in self._fuse_off}
            self._fused_fit_programs = {fp.vocab_id: fp
                                        for fp in plan.fit_dataflows
                                        if fp.legal}
            self._active_groups = [g for g in plan.groups
                                   if all(o in self._fused_programs
                                          for o in g.outputs)]
            self._grouped_outputs = {o: gi
                                     for gi, g in enumerate(self._active_groups)
                                     for o in g.outputs}
        self.state = PipelineState(
            tables={vf.vocab_id: np.full(vf.capacity, -1, np.int32)
                    for vf in plan.vocab_fits},
            n_unique={vf.vocab_id: 0 for vf in plan.vocab_fits},
            version=0)
        self._source_nodes = {n.id: n for n in graph.nodes
                              if n.kind == NodeType.SOURCE}
        self._resolved_cache: tuple = (-1, {})
        self._staged_cache: tuple = (-1, ({}, {}))
        self._staged_vocab_ids: list[str] = []
        # fit closure source buffers, computed once (used by all fit paths)
        self._fit_bufs = plan.fit_source_buffers()
        if backend != "numpy":
            self._apply_fn = self._build_apply()
            self._apply_jit = jax.jit(self._apply_fn)
            self._fit_chunk_fn = self._build_fit_chunk()
            self._fit_chunk_jit = jax.jit(self._fit_chunk_fn)

    # ------------------------------------------------------------------
    # knob recompilation (the controller's row_tile / fuse actuator)
    # ------------------------------------------------------------------

    def fuse_spec(self):
        """The current fuse setting in ``with_knobs``-compatible form:
        ``"off"``, ``"auto"``, or the frozenset of staged-forced outputs."""
        if self.fuse == "off":
            return "off"
        return frozenset(self._fuse_off) if self._fuse_off else "auto"

    def with_knobs(self, *, row_tile: Optional[int] = None, fuse=None):
        """Recompile this pipeline at new knob settings, SHARING vocabulary
        state with the original.

        ``row_tile`` retiles every fused kernel (legality is re-judged at
        the new tile — a tile that no longer fits the VMEM budget falls
        back staged, never crashes); ``fuse`` takes the same forms as the
        constructor ("auto"/"off"/per-output spec).  Omitted knobs keep
        their current values.  The returned pipeline aliases ``self.state``
        — tables fitted on either are visible to both, so a mid-run swap
        (``StreamingExecutor.swap_pipeline``) is bit-identical to a fresh
        compile at the same settings (pinned by tests/test_controller.py).
        """
        new_tile = (self.plan.row_tile if row_tile is None
                    else max(1, int(row_tile)))
        new_fuse = self.fuse_spec() if fuse is None else fuse
        # re-judge all fusion programs from scratch at the new tile; the
        # constructor re-resolves compiled-mode legality (and re-optimizes)
        # exactly as a fresh compile would
        plan = dataclasses.replace(
            self.plan, dataflows=[], fit_dataflows=[], groups=[],
            opt_info={}, compiled_mode=False, row_tile=new_tile)
        build_plan_programs(plan)
        new = CompiledPipeline(plan, self.graph, self.backend,
                               interpret=self.interpret, name=self.name,
                               fuse=new_fuse, optimize=self.optimize,
                               semantics=self.semantics)
        new.state = self.state
        return new

    # ------------------------------------------------------------------
    # source assembly: raw columnar batch -> source buffers
    # ------------------------------------------------------------------

    def _gather_sources(self, raw: dict, buffers=None) -> dict:
        """numpy backend: assemble column blocks on the host.

        jnp/pallas backends assemble INSIDE the jit (§Perf E1): the host-side
        np.stack/transpose of the hex columns cost ~1/3 of apply wall time;
        on device it fuses into the first kernel's read."""
        out = {}
        for buf in (self.plan.source_buffers if buffers is None else buffers):
            node = self._source_nodes[buf]
            feats = node.features
            if feats[0].seq_len:  # token column: (rows, seq)
                out[buf] = np.asarray(raw[feats[0].name])
            elif feats[0].is_hex:
                cols = np.stack([np.asarray(raw[f.name]) for f in feats], axis=1)
                out[buf] = cols  # (rows, n, w)
            else:
                cols = [np.asarray(raw[f.name]) for f in feats]
                out[buf] = np.stack(cols, axis=1)
        return out

    def _raw_columns(self, raw: dict, buffers=None) -> dict:
        """Pass-through of the raw columns needed by the source buffers."""
        cols = {}
        for buf in (self.plan.source_buffers if buffers is None else buffers):
            for f in self._source_nodes[buf].features:
                cols[f.name] = np.asarray(raw[f.name])
        return cols

    def _assemble_sources_jnp(self, cols: dict, buffers=None) -> dict:
        """Device-side source assembly (traced; part of the jit program)."""
        out = {}
        for buf in (self.plan.source_buffers if buffers is None else buffers):
            node = self._source_nodes[buf]
            feats = node.features
            if feats[0].seq_len:
                out[buf] = cols[feats[0].name]
            elif feats[0].is_hex:
                stacked = jnp.stack([cols[f.name] for f in feats], axis=1)
                out[buf] = jnp.moveaxis(stacked, -1, 0)  # digit-major
            else:
                out[buf] = jnp.stack([cols[f.name] for f in feats], axis=1)
        return out

    # ------------------------------------------------------------------
    # stage interpreters
    # ------------------------------------------------------------------

    def _run_stages_numpy(self, bufs: dict, stage_ids=None,
                          state: Optional[PipelineState] = None) -> dict:
        # state is an explicit snapshot so one batch never mixes two
        # vocabulary versions when an online refit swaps self.state mid-run
        state = self.state if state is None else state
        for s in self.plan.stages:
            if stage_ids is not None and s.stage_id not in stage_ids:
                continue
            if isinstance(s, FusedStage):
                bufs[s.out_buf] = _chain_numpy(s, bufs[s.in_buf])
            elif isinstance(s, CrossStage):
                bufs[s.out_buf] = s.op.numpy2(bufs[s.in_a], bufs[s.in_b])
            elif isinstance(s, OneHotStage):
                bufs[s.out_buf] = s.op.numpy(bufs[s.in_buf])
            elif isinstance(s, VocabLookupStage):
                tbl = state.tables[s.vocab_id]
                vm = ops_lib.VocabMap(s.capacity)
                bufs[s.out_buf] = vm.numpy_apply(bufs[s.in_buf], tbl)
            else:
                raise NotImplementedError(type(s))
        return bufs

    def _stage_fns(self, needed_ids: Optional[set] = None) -> dict:
        """Per-stage jnp/pallas callables keyed by stage_id.

        ``needed_ids`` restricts codegen to the stages the staged path will
        actually run (fused outputs bypass per-stage kernels entirely).
        """
        fns = {}
        for s in self.plan.stages:
            if needed_ids is not None and s.stage_id not in needed_ids:
                continue
            if isinstance(s, FusedStage):
                chain = _chain_fn(s)
                if self.backend == "pallas":
                    fns[s.stage_id] = kops.fused_stage(
                        chain, in_dtype=s.in_dtype, out_dtype=s.out_dtype,
                        hex_width=s.in_hex_width,
                        block_rows=32 * s.lanes,
                        block_cols=4 * s.vector_width,
                        interpret=self.interpret)
                else:
                    fns[s.stage_id] = chain
            elif isinstance(s, CrossStage):
                fns[s.stage_id] = s.op.jnp_expr2
            elif isinstance(s, OneHotStage):
                fns[s.stage_id] = s.op.jnp_expr
            elif isinstance(s, VocabLookupStage):
                parts = 1 if s.placement == "vmem" else max(
                    1, (4 * s.capacity) // (4 << 20))
                if self.backend == "pallas":
                    def mk(parts=parts):
                        def f(x, tbl, n):
                            return kops.vocab_lookup(x, tbl, n, partitions=parts,
                                                     interpret=self.interpret)
                        return f
                    fns[s.stage_id] = mk()
                else:
                    fns[s.stage_id] = kref.vocab_lookup
        return fns

    def _build_dataflow_fn(self, po: PackOutput, dp: DataflowProgram):
        """Lower one legal DataflowProgram to its single streaming kernel."""
        plan = self.plan
        inputs = [StreamInput(b, plan.buffers[b].width, plan.buffers[b].dtype,
                              plan.buffers[b].hex_width)
                  for b in dp.source_buffers]
        steps, tables = self._dataflow_steps(dp.stage_ids, dp.vocab_ids)
        terminals = [(b, plan.buffers[b].width) for b in po.buffers]
        return kops.output_dataflow(inputs, tables, steps, terminals,
                                    po.dtype, pad_cols_to=po.pad_cols_to,
                                    block_rows=plan.row_tile,
                                    interpret=self.interpret,
                                    vmem_limit_bytes=kernel_vmem_limit(plan))

    def _dataflow_steps(self, stage_ids, vocab_ids):
        """TileStep program + TableInput list for an apply-side slice
        (lookup steps resolved against the slice's vocab table order)."""
        tbl_index = {vid: i for i, vid in enumerate(vocab_ids)}
        tables: list = [None] * len(vocab_ids)
        steps = []
        for sid in stage_ids:
            s = self.plan.stage_by_id(sid)
            if isinstance(s, VocabLookupStage):
                idx = tbl_index[s.vocab_id]
                tables[idx] = TableInput(s.vocab_id, s.capacity)
                steps.append(TileStep("lookup", s.out_buf, (s.in_buf,),
                                      table=idx))
            else:
                steps.extend(self._tile_steps([sid]))
        return steps, tables

    def _build_group_fn(self, group: DataflowGroup):
        """Lower one DataflowGroup to its single multi-output kernel."""
        plan = self.plan
        inputs = [StreamInput(b, plan.buffers[b].width, plan.buffers[b].dtype,
                              plan.buffers[b].hex_width)
                  for b in group.source_buffers]
        steps, tables = self._dataflow_steps(group.stage_ids, group.vocab_ids)
        outs = []
        for name in group.outputs:
            po = next(p for p in plan.pack if p.name == name)
            outs.append(GroupOutput(
                name, tuple((b, plan.buffers[b].width) for b in po.buffers),
                po.dtype, po.pad_cols_to))
        return kops.group_dataflow(inputs, tables, steps, outs,
                                   block_rows=plan.row_tile,
                                   interpret=self.interpret,
                                   vmem_limit_bytes=kernel_vmem_limit(plan))

    def _tile_steps(self, stage_ids) -> list[TileStep]:
        """Shared TileStep codegen for the fused apply/fit kernel bodies
        (lookup steps are resolved by the apply-side caller)."""
        steps: list[TileStep] = []
        for sid in stage_ids:
            s = self.plan.stage_by_id(sid)
            if isinstance(s, FusedStage):
                steps.append(TileStep("map", s.out_buf, (s.in_buf,),
                                      fn=_chain_fn(s)))
            elif isinstance(s, CrossStage):
                steps.append(TileStep("join", s.out_buf, (s.in_a, s.in_b),
                                      fn=s.op.jnp_expr2))
            elif isinstance(s, OneHotStage):
                # lane-aligned in-kernel form: same values as op.jnp_expr,
                # but without the trailing-axis reshape Mosaic rejects
                steps.append(TileStep(
                    "map", s.out_buf, (s.in_buf,),
                    fn=(lambda x, d=s.op.depth: lanes.onehot_lanes(x, d))))
            else:  # pragma: no cover - legality passes reject these
                raise NotImplementedError(type(s))
        return steps

    def _build_fit_dataflow_fn(self, fp: FitProgram):
        """Lower one legal FitProgram to its single streaming fit kernel."""
        plan = self.plan
        inputs = [StreamInput(b, plan.buffers[b].width, plan.buffers[b].dtype,
                              plan.buffers[b].hex_width)
                  for b in fp.source_buffers]
        steps = self._tile_steps(fp.stage_ids)
        return kops.fit_dataflow(inputs, steps, fp.in_buf, fp.capacity,
                                 block_rows=plan.row_tile,
                                 interpret=self.interpret,
                                 vmem_limit_bytes=kernel_vmem_limit(plan))

    def _build_apply(self) -> Callable:
        plan = self.plan
        fused = self._fused_programs
        staged_pos = [po for po in plan.pack if po.name not in fused]
        if fused:
            staged_ids: set = set()
            for po in staged_pos:
                staged_ids.update(plan.output_slice(po))
        else:
            staged_ids = {s.stage_id for s in plan.stages}
        # raw tables only reach the device for staged lookups; fully fused
        # vocabularies travel solely as their cached OOV-resolved form
        self._staged_vocab_ids = sorted(
            s.vocab_id for s in plan.stages
            if isinstance(s, VocabLookupStage) and s.stage_id in staged_ids)
        dfmap = {dp.output: dp for dp in plan.dataflows}
        fns = self._stage_fns(staged_ids)
        grouped = self._grouped_outputs
        group_fns = [self._build_group_fn(g) for g in self._active_groups]
        dataflows = {name: self._build_dataflow_fn(
                         next(po for po in plan.pack if po.name == name), dp)
                     for name, dp in fused.items() if name not in grouped}
        packers = {}
        if self.backend == "pallas":
            for po in staged_pos:
                widths = [plan.buffers[b].width for b in po.buffers]
                dts = [plan.buffers[b].dtype for b in po.buffers]
                packers[po.name] = kops.packer(
                    widths, dts, po.dtype, pad_cols_to=po.pad_cols_to,
                    block_rows=plan.row_tile,
                    interpret=self.interpret)

        def apply_fn(tables, n_uniques, resolved, cols):
            bufs = dict(self._assemble_sources_jnp(cols))
            for s in plan.stages:
                if s.stage_id not in staged_ids:
                    continue
                if isinstance(s, FusedStage):
                    bufs[s.out_buf] = fns[s.stage_id](bufs[s.in_buf])
                elif isinstance(s, CrossStage):
                    bufs[s.out_buf] = fns[s.stage_id](bufs[s.in_a], bufs[s.in_b])
                elif isinstance(s, OneHotStage):
                    bufs[s.out_buf] = fns[s.stage_id](bufs[s.in_buf])
                elif isinstance(s, VocabLookupStage):
                    bufs[s.out_buf] = fns[s.stage_id](
                        bufs[s.in_buf], tables[s.vocab_id],
                        n_uniques[s.vocab_id])
            # each DataflowGroup issues ONE kernel for all member outputs;
            # shared stages execute once per tile for the whole group
            gout = {}
            for g, gfn in zip(self._active_groups, group_fns):
                args = ([bufs[b] for b in g.source_buffers]
                        + [resolved[vid] for vid in g.vocab_ids])
                for name, packed in zip(g.outputs, gfn(*args)):
                    gout[name] = packed
            out = {}
            for po in plan.pack:
                dp = dfmap.get(po.name)
                if po.name in gout:
                    packed = gout[po.name]
                    out[po.name] = packed[:, 0] if po.squeeze else packed
                    continue
                if po.name in fused:
                    args = ([bufs[b] for b in dp.source_buffers]
                            + [resolved[vid] for vid in dp.vocab_ids])
                    packed = dataflows[po.name](*args)
                    out[po.name] = packed[:, 0] if po.squeeze else packed
                    continue
                blocks = [bufs[b] for b in po.buffers]
                if self.backend == "pallas" and not po.squeeze:
                    out[po.name] = packers[po.name](*blocks)
                else:
                    packed = kref.pack_blocks(blocks, po.dtype, po.pad_cols_to)
                    out[po.name] = packed[:, 0] if po.squeeze else packed
            return out

        return apply_fn

    def _build_fit_chunk(self) -> Callable:
        """One streamed fit chunk: chunk first-occurrence positions + counts.

        Legally-fused vocabs (pallas backend, ``fuse="auto"``) run their
        whole chunk — upstream chains, hex decode, and the build — as ONE
        streaming kernel (``kernels/dataflow.make_fit_dataflow``), with no
        HBM tensor between upstream stages and ``vocab_build_chunk``.  The
        rest take the staged path (per-stage kernels, then the build kernel),
        restricted to exactly the stages the staged vocabs still need.
        """
        plan = self.plan
        fused_fit = self._fused_fit_programs
        staged_vfs = [vf for vf in plan.vocab_fits
                      if vf.vocab_id not in fused_fit]
        if fused_fit:
            staged_ids: set = set()
            for vf in staged_vfs:
                staged_ids.update(plan.fit_slice(vf))
        else:
            staged_ids = set(plan.fit_stage_ids)
        fns = self._stage_fns(staged_ids)
        fit_kernels = {vid: self._build_fit_dataflow_fn(fp)
                       for vid, fp in fused_fit.items()}
        builds = {}
        for vf in staged_vfs:
            parts = 1 if vf.placement == "vmem" else max(
                1, (4 * vf.capacity) // (4 << 20))
            if self.backend == "pallas":
                def mk(vf=vf, parts=parts):
                    def f(vals):
                        return kops.vocab_build_chunk(
                            vals, capacity=vf.capacity, partitions=parts,
                            interpret=self.interpret)
                    return f
                builds[vf.vocab_id] = mk()
            else:
                builds[vf.vocab_id] = (
                    lambda vals, vf=vf: kref.vocab_build_chunk(vals, vf.capacity))

        fit_bufs = self._fit_bufs

        def fit_chunk(cols):
            bufs = dict(self._assemble_sources_jnp(cols, fit_bufs))
            for s in plan.stages:
                if s.stage_id not in staged_ids:
                    continue
                if isinstance(s, FusedStage):
                    bufs[s.out_buf] = fns[s.stage_id](bufs[s.in_buf])
                elif isinstance(s, CrossStage):
                    bufs[s.out_buf] = fns[s.stage_id](bufs[s.in_a], bufs[s.in_b])
                elif isinstance(s, OneHotStage):
                    bufs[s.out_buf] = fns[s.stage_id](bufs[s.in_buf])
                elif isinstance(s, VocabLookupStage):
                    raise AssertionError("lookup cannot precede fit")
            out = {}
            for vf in plan.vocab_fits:
                if vf.vocab_id in fit_kernels:
                    fp = fused_fit[vf.vocab_id]
                    out[vf.vocab_id] = fit_kernels[vf.vocab_id](
                        *(bufs[b] for b in fp.source_buffers))
                    continue
                vals = bufs[vf.in_buf]
                # first-occurrence positions + counts (frequency filter)
                out[vf.vocab_id] = (
                    builds[vf.vocab_id](vals),
                    kref.vocab_counts_chunk(vals.reshape(-1), vf.capacity))
            return out

        return fit_chunk

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def fit(self, batch_iter) -> PipelineState:
        """Stream batches; learn vocabulary tables (paper's fit phase)."""
        if not self.plan.vocab_fits:
            self.state = dataclasses.replace(self.state,
                                             version=self.state.version + 1)
            return self.state
        tables, n_unique = self._fit_tables(batch_iter)
        self.state = PipelineState(tables=tables, n_unique=n_unique,
                                   version=self.state.version + 1)
        return self.state

    def fit_incremental(self, batch_iter) -> PipelineState:
        """Online vocabulary refresh over a window of NEW events.

        Unlike ``fit`` (which rebuilds the tables from scratch), this merges
        the window into the current state **rank-stably**: every value the
        pipeline already admitted keeps its rank — so embedding rows learned
        by a live trainer keep their meaning across the swap — and values
        first seen in the window are appended in first-occurrence order at
        ranks ``n_unique ..``.  The frequency filter (``min_count``) applies
        per window.  The swap is a single attribute store of a fresh
        ``PipelineState`` with a version bump, so concurrent apply calls
        (which snapshot the state once per batch) are each served by exactly
        one version, and the per-version resolved/staged table caches refresh
        automatically.
        """
        cur = self.state
        if not self.plan.vocab_fits:
            self.state = dataclasses.replace(cur, version=cur.version + 1)
            return self.state
        win_tables, _ = self._fit_tables(batch_iter)
        tables, n_unique = {}, {}
        for vid, wt in win_tables.items():
            base = np.asarray(cur.tables[vid])
            n = int(cur.n_unique[vid])
            wt = np.asarray(wt)
            new_vals = np.flatnonzero((wt >= 0) & (base < 0))
            order = np.argsort(wt[new_vals], kind="stable")
            merged = base.copy()
            merged[new_vals[order]] = n + np.arange(len(new_vals),
                                                    dtype=np.int32)
            tables[vid] = merged
            n_unique[vid] = n + int(len(new_vals))
        self.state = PipelineState(tables=tables, n_unique=n_unique,
                                   version=cur.version + 1)
        return self.state

    def _fit_tables(self, batch_iter) -> tuple:
        """Run the (fused) chunked fit machinery over ``batch_iter`` and
        return ``(tables, n_unique)`` without touching ``self.state``."""
        if self.backend == "numpy":
            gens = {vf.vocab_id: ops_lib.VocabGen(vf.capacity,
                                                  min_count=vf.min_count)
                    for vf in self.plan.vocab_fits}
            states = {vid: g.init_state() for vid, g in gens.items()}
            offset = 0
            fit_bufs = self._fit_bufs
            for raw in batch_iter:
                bufs = self._gather_sources(raw, fit_bufs)
                bufs = self._run_stages_numpy(bufs,
                                              set(self.plan.fit_stage_ids))
                n_elems = 0
                for vf in self.plan.vocab_fits:
                    vals = bufs[vf.in_buf].reshape(-1)
                    n_elems = max(n_elems, vals.size)
                    states[vf.vocab_id] = gens[vf.vocab_id].update(
                        states[vf.vocab_id], vals, offset)
                offset += n_elems
            tables = {vid: gens[vid].finalize(st) for vid, st in states.items()}
        else:
            states = {vf.vocab_id: kref.vocab_state_init(vf.capacity)
                      for vf in self.plan.vocab_fits}
            mincounts = {vf.vocab_id: vf.min_count
                         for vf in self.plan.vocab_fits}
            fit_bufs = self._fit_bufs
            for ci, raw in enumerate(batch_iter):
                sources = {k: jnp.asarray(v)
                           for k, v in self._raw_columns(raw, fit_bufs).items()}
                chunk_fps = self._fit_chunk_jit(sources)
                for vid, (fp, cnt) in chunk_fps.items():
                    states[vid] = kref.vocab_merge(states[vid], fp, ci,
                                                   chunk_counts=cnt)
            tables = {vid: np.asarray(kref.vocab_finalize(
                          st, min_count=mincounts[vid]))
                      for vid, st in states.items()}
        n_unique = {vid: ops_lib.VocabGen.n_unique(t)
                    for vid, t in tables.items()}
        return tables, n_unique

    def _resolved_tables(self, state: Optional[PipelineState] = None) -> dict:
        """OOV-resolved (1, capacity) tables for the fused kernels' gathers:
        table'[v] = rank if present else n_unique.  Computed once per state
        version — tables only change at fit/swap time, so the apply hot path
        never pays the O(capacity) fold per batch."""
        state = self.state if state is None else state
        fused_vids = {vid for dp in self._fused_programs.values()
                      for vid in dp.vocab_ids}
        if not fused_vids:
            return {}
        ver, cached = self._resolved_cache
        if ver == state.version:
            return cached
        resolved = {}
        for vid in sorted(fused_vids):
            t = np.asarray(state.tables[vid])
            n = state.n_unique[vid]
            resolved[vid] = jnp.asarray(
                np.where(t >= 0, t, n).astype(np.int32).reshape(1, -1))
        self._resolved_cache = (state.version, resolved)
        return resolved

    def _staged_table_args(self, state: Optional[PipelineState] = None) -> tuple:
        """Device-resident raw tables + n_unique scalars for the staged
        lookups only, uploaded once per state version (fully fused
        vocabularies never ship their raw table to the apply program)."""
        state = self.state if state is None else state
        ver, cached = self._staged_cache
        if ver == state.version:
            return cached
        tables = {vid: jnp.asarray(state.tables[vid])
                  for vid in self._staged_vocab_ids}
        n_uniq = {vid: jnp.asarray(state.n_unique[vid], jnp.int32)
                  for vid in self._staged_vocab_ids}
        self._staged_cache = (state.version, (tables, n_uniq))
        return tables, n_uniq

    def apply_versioned(self, raw_batch: dict) -> tuple:
        """Apply one batch against a single state snapshot and return
        ``(packed, version)`` — the snapshot is read exactly once, so a
        concurrent ``fit_incremental`` swap can never serve one batch a mix
        of two vocabulary versions, and the caller learns which version
        transformed the batch (``repro.online`` tags delivered batches
        with it)."""
        state = self.state
        if self.backend == "numpy":
            sources = self._gather_sources(raw_batch)
            bufs = self._run_stages_numpy(dict(sources), state=state)
            out = {}
            for po in self.plan.pack:
                blocks = [bufs[b] for b in po.buffers]
                rows = blocks[0].shape[0]
                cat = np.concatenate(
                    [np.asarray(b, dtype=po.dtype).reshape(rows, -1)
                     for b in blocks], axis=1)
                padded = -(-cat.shape[1] // po.pad_cols_to) * po.pad_cols_to
                if padded != cat.shape[1]:
                    cat = np.pad(cat, ((0, 0), (0, padded - cat.shape[1])))
                out[po.name] = cat[:, 0] if po.squeeze else cat
            return out, state.version
        tables, n_uniq = self._staged_table_args(state)
        cols = {k: jnp.asarray(v) for k, v in self._raw_columns(raw_batch).items()}
        return (self._apply_jit(tables, n_uniq, self._resolved_tables(state),
                                cols), state.version)

    def __call__(self, raw_batch: dict) -> dict:
        """Apply phase: raw columnar batch -> packed training-ready tensors."""
        return self.apply_versioned(raw_batch)[0]

    def referenced_columns(self) -> list:
        """Raw columns the apply program reads (projection-pushdown set)."""
        return self.plan.referenced_columns()

    # stats used by benchmarks / Table-4 analogue
    def resource_summary(self) -> dict:
        return self.plan.resource_summary()

    def optimize_report(self) -> dict:
        """What the optimizer pass did to the compiled plan (see
        ``ExecutionPlan.optimize_report``); ``optimized=False`` with zero
        counts when compiled with ``optimize="off"``."""
        return self.plan.optimize_report()

    def lowering_report(self) -> dict:
        """Per-output lowering decision: grouped / fused / staged.

        Keys are PackOutput names; ``path`` is "grouped" (member of a
        multi-output fused dataflow — ``group`` lists the members sharing
        the kernel), "fused" (own single streaming kernel) or "staged".
        For staged outputs ``reason`` says what fell back and
        ``reason_kind`` classifies *why*: "budget" (VMEM working set),
        "stage-kind" (no tile codegen for a stage), "hbm-table"
        (HBM-resident vocab), "hex-terminal", "mosaic-illegal" (fits the
        logical budget but not the compiled lowering's lane-padded /
        gather-scratch one — interpret mode would fuse it), or "" when
        the backend/fuse mode simply has no tile codegen.
        """
        dfmap = {dp.output: dp for dp in self.plan.dataflows}
        groups = {name: self._active_groups[gi]
                  for name, gi in self._grouped_outputs.items()}
        rep = {}
        for po in self.plan.pack:
            dp = dfmap.get(po.name)
            if po.name in groups:
                path = "grouped"
            elif po.name in self._fused_programs:
                path = "fused"
            else:
                path = "staged"
            rep[po.name] = {
                "path": path,
                "group": list(groups[po.name].outputs)
                         if po.name in groups else [],
                "legal": dp.legal if dp else False,
                "reason": dp.reason if dp else "no dataflow program planned",
                "reason_kind": dp.reason_kind if dp else "",
                "n_stages": dp.n_stages if dp else 0,
                "vocab_ids": list(dp.vocab_ids) if dp else [],
            }
        return rep

    def fit_lowering_report(self) -> dict:
        """Per-vocab fit lowering decision: fused single-kernel vs staged.

        Keys are vocab ids; ``path`` is "fused" or "staged"; for staged
        vocabs ``reason`` says what fell back and ``reason_kind``
        classifies why (same taxonomy as ``lowering_report``; "" means the
        backend/fuse mode simply has no fit tile codegen).
        """
        fpmap = {fp.vocab_id: fp for fp in self.plan.fit_dataflows}
        rep = {}
        for vf in self.plan.vocab_fits:
            fp = fpmap.get(vf.vocab_id)
            rep[vf.vocab_id] = {
                "path": ("fused" if vf.vocab_id in self._fused_fit_programs
                         else "staged"),
                "legal": fp.legal if fp else False,
                "reason": fp.reason if fp else "no fit program planned",
                "reason_kind": fp.reason_kind if fp else "",
                "n_stages": fp.n_stages if fp else 0,
                "placement": vf.placement,
            }
        return rep

    def stage_execution_counts(self, phase: str = "apply") -> dict:
        """Static per-batch execution count for every plan stage.

        Derived from the lowering decisions (kernel bodies only run at
        trace time under jit, so dynamic counters cannot observe this):
        a stage on the staged path executes once per batch regardless of
        consumer count; a stage in k solo fused kernels re-executes k
        times (once per kernel body); a stage in a DataflowGroup executes
        exactly once for the whole group — the acceptance check that
        shared prefixes run once per batch under the grouped lowering.
        """
        if phase not in ("apply", "fit"):
            raise ValueError(f"unknown phase {phase!r}")
        plan = self.plan
        if phase == "fit":
            counts = {sid: 0 for sid in plan.fit_stage_ids}
            staged_ids: set = set()
            for vf in plan.vocab_fits:
                if vf.vocab_id not in self._fused_fit_programs:
                    staged_ids.update(plan.fit_slice(vf))
            for sid in staged_ids:
                counts[sid] += 1
            for fp in self._fused_fit_programs.values():
                for sid in fp.stage_ids:
                    counts[sid] += 1
            return counts
        counts = {s.stage_id: 0 for s in plan.stages}
        staged_ids = set()
        for po in plan.pack:
            if po.name not in self._fused_programs:
                staged_ids.update(plan.output_slice(po))
        for sid in staged_ids:
            counts[sid] += 1
        for g in self._active_groups:
            for sid in g.stage_ids:
                counts[sid] += 1
        for name, dp in self._fused_programs.items():
            if name in self._grouped_outputs:
                continue
            for sid in dp.stage_ids:
                counts[sid] += 1
        return counts

    def traced_pallas_call_count(self, raw_batch: dict,
                                 phase: str = "apply") -> int:
        """Number of pallas_call primitives a phase's program traces to.

        ``phase="apply"``: the grouped lowering traces one streaming kernel
        per DataflowGroup plus one per solo fused output — strictly fewer
        calls than outputs whenever grouping engaged (the acceptance
        invariant); the ungrouped fused lowering traces exactly one call
        per output; the staged lowering traces one call per stage plus one
        per packer.  ``phase="fit"``: the fused fit chunk traces one call
        per legally-fused vocab (plus the staged kernels of any fallback
        vocab).
        """
        if phase not in ("apply", "fit"):
            raise ValueError(f"unknown phase {phase!r}")
        if self.backend == "numpy":
            return 0
        if phase == "fit":
            cols = {k: jnp.asarray(v) for k, v in
                    self._raw_columns(raw_batch, self._fit_bufs).items()}
            jaxpr = jax.make_jaxpr(self._fit_chunk_fn)(cols)
            return count_pallas_calls(jaxpr)
        tables, n_uniq = self._staged_table_args()
        cols = {k: jnp.asarray(v)
                for k, v in self._raw_columns(raw_batch).items()}
        jaxpr = jax.make_jaxpr(self._apply_fn)(tables, n_uniq,
                                               self._resolved_tables(), cols)
        return count_pallas_calls(jaxpr)
