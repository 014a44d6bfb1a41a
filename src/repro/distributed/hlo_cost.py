"""Trip-count-aware HLO cost analysis.

XLA's ``compiled.cost_analysis()`` counts a while-loop body ONCE, regardless
of trip count (verified empirically) — with scan-over-layers models this
undercounts flops/bytes/collectives by 1-3 orders of magnitude.  This module
re-derives the costs from the post-optimization HLO text, recursively
expanding ``while`` bodies (x trip count), ``fusion``/``call`` computations,
and inventorying collectives with the correct multipliers.

Conventions (mirroring HloCostAnalysis):
- dot: 2 x elems(output) x prod(contracted dims)
- elementwise arithmetic: 1 flop / output element; transcendentals tracked
  separately
- bytes accessed: operands + outputs of top-level instructions (fusion
  internals stay in registers — only the fusion's own operands/outputs touch
  HBM); parameter/constant/tuple plumbing excluded
- while trip count: parsed from the loop condition's comparison constant
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "token": 0,
    "opaque": 0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# instruction: [ROOT] %name = <shape(s)> opcode(<operands...>)<attrs>
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*?)\s+([\w\-]+)\((.*)$")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->")

_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "compare", "select", "and", "or", "xor", "not", "clamp",
    "remainder", "shift-left", "shift-right-logical", "shift-right-arithmetic",
    "floor", "ceil", "round-nearest-afz", "round-nearest-even", "sign",
    "is-finite", "popcnt", "clz",
}
_TRANSCENDENTAL = {"exponential", "log", "log-plus-one", "exponential-minus-one",
                   "power", "tanh", "logistic", "rsqrt", "sqrt", "cbrt",
                   "sine", "cosine", "tan", "atan2", "erf"}
_SKIP_BYTES = {"parameter", "constant", "tuple", "get-tuple-element",
               "bitcast", "after-all", "partition-id", "replica-id", "iota"}
_COLLECTIVES = {"all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "all-reduce-start", "all-gather-start",
                "collective-permute-start", "reduce-scatter-start",
                "all-to-all-start"}


def _shape_elems_bytes(shape_str: str) -> tuple[int, int]:
    """Total elements and bytes across all array shapes in the string."""
    elems = 0
    bts = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        elems += n
        bts += n * _DTYPE_BYTES[dt]
    return elems, bts


def _first_shape_dims(shape_str: str) -> list[int]:
    m = _SHAPE_RE.search(shape_str)
    if not m or not m.group(2):
        return []
    return [int(d) for d in m.group(2).split(",")]


@dataclasses.dataclass
class Instr:
    name: str
    shape: str
    opcode: str
    rest: str  # operand list + attrs (raw tail of the line)

    def operands(self) -> list[str]:
        """Operand instruction names from the first paren group."""
        depth = 1
        out = []
        cur = ""
        for ch in self.rest:
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
            cur += ch
        for tok in re.findall(r"%([\w\.\-]+)", cur):
            out.append(tok)
        return out

    def attr(self, key: str):
        m = re.search(rf"{key}=%?([\w\.\-]+)", self.rest)
        return m.group(1) if m else None

    def attr_braced(self, key: str):
        m = re.search(rf"{key}=\{{([^}}]*)\}}", self.rest)
        return m.group(1) if m else None


@dataclasses.dataclass
class CostTotals:
    flops: float = 0.0
    transcendentals: float = 0.0
    bytes_accessed: float = 0.0
    collectives: dict = dataclasses.field(
        default_factory=lambda: defaultdict(
            lambda: {"count": 0.0, "bytes": 0.0, "wire_bytes": 0.0}))

    def add(self, other: "CostTotals", mult: float = 1.0):
        self.flops += other.flops * mult
        self.transcendentals += other.transcendentals * mult
        self.bytes_accessed += other.bytes_accessed * mult
        for k, v in other.collectives.items():
            st = self.collectives[k]
            for f in ("count", "bytes", "wire_bytes"):
                st[f] += v[f] * mult


class HloCostModel:
    def __init__(self, hlo_text: str):
        self.comps: dict[str, list[Instr]] = {}
        self.entry: str = ""
        self._parse(hlo_text)
        self._cache: dict[str, CostTotals] = {}
        # instruction names are unique module-wide in HLO text
        self._producers: dict[str, Instr] = {
            i.name: i for instrs in self.comps.values() for i in instrs}

    # ---------------- parsing ----------------

    def _parse(self, text: str):
        cur = None
        for raw in text.splitlines():
            line = raw.rstrip()
            if not line or line.startswith("//"):
                continue
            if not line.startswith(" ") and line.endswith("{"):
                m = _COMP_HDR_RE.match(line.strip(" {"))
                if m:
                    cur = m.group(1)
                    self.comps[cur] = []
                    if line.lstrip().startswith("ENTRY"):
                        self.entry = cur
                    continue
            if line.strip() == "}":
                cur = None
                continue
            if cur is None:
                continue
            m = _INSTR_RE.match(line)
            if m:
                self.comps[cur].append(
                    Instr(m.group(1), m.group(2), m.group(3), m.group(4)))

    def _symtab(self, comp: str) -> dict[str, str]:
        return {i.name: i.shape for i in self.comps.get(comp, [])}

    # ---------------- trip counts ----------------

    def _trip_count(self, cond_comp: str) -> int:
        """Best-effort: the largest integer constant in the loop condition."""
        best = 1
        for i in self.comps.get(cond_comp, []):
            if i.opcode == "constant":
                m = re.search(r"constant\((-?\d+)\)", i.opcode + "(" + i.rest)
                if m:
                    best = max(best, int(m.group(1)))
        return best

    # ---------------- per-instruction costs ----------------

    @staticmethod
    def _group_size(rest: str) -> int:
        m = re.search(r"replica_groups=\[([\d,]+)\]<=\[\d+\]", rest)
        if m:
            dims = [int(x) for x in m.group(1).split(",")]
            return dims[-1] if len(dims) > 1 else dims[0]
        m = re.search(r"replica_groups=\{\{([^}]*)\}", rest)
        if m:
            return max(len([t for t in m.group(1).split(",") if t.strip()]), 1)
        return 2

    def _dot_flops(self, ins: Instr, symtab: dict) -> float:
        out_elems, _ = _shape_elems_bytes(ins.shape)
        ops = ins.operands()
        lhs_shape = symtab.get(ops[0], "") if ops else ""
        lhs_dims = _first_shape_dims(lhs_shape)
        contract = ins.attr_braced("lhs_contracting_dims")
        k = 1
        if contract and lhs_dims:
            for idx in contract.split(","):
                idx = idx.strip()
                if idx:
                    k *= lhs_dims[int(idx)]
        return 2.0 * out_elems * k

    def _upcast_factor(self, ins: Instr) -> float:
        """1.0, or <1 when the (first) operand is a pure dtype upcast."""
        ops_ = ins.operands()
        if not ops_:
            return 1.0
        producer = self._producers.get(ops_[0])
        if producer is None:
            return 1.0
        if producer.opcode == "convert" or (
                producer.opcode == "fusion" and "convert" in producer.name):
            pin = producer.operands()
            if pin:
                src_ins = self._producers.get(pin[0])
                src = src_ins.shape if src_ins is not None else ""
                _, src_b = _shape_elems_bytes(src)
                _, dst_b = _shape_elems_bytes(producer.shape)
                if src_b and dst_b and src_b < dst_b:
                    return src_b / dst_b
        return 1.0

    def _fused_param_bytes(self, comp: str, param_idx: int):
        """If parameter(param_idx) of a fused computation is consumed ONLY by
        slicing ops, return the summed slice-output bytes; else None."""
        instrs = self.comps.get(comp)
        if not instrs:
            return None
        pname = None
        for i in instrs:
            if i.opcode == "parameter" and i.rest.startswith(f"{param_idx})"):
                pname = i.name
                break
        if pname is None:
            return None
        sliced = 0
        for i in instrs:
            if pname in i.operands():
                if i.opcode in ("dynamic-slice", "slice", "gather"):
                    _, b = _shape_elems_bytes(i.shape)
                    sliced += b
                elif i.opcode in ("bitcast", "copy", "reshape", "transpose"):
                    return None  # consumed wholesale via a reshape chain
                else:
                    return None
        return sliced if sliced else None

    # ---------------- computation walk ----------------

    def cost(self, comp: str) -> CostTotals:
        if comp in self._cache:
            return self._cache[comp]
        total = CostTotals()
        self._cache[comp] = total  # break cycles defensively
        symtab = self._symtab(comp)
        for ins in self.comps.get(comp, []):
            op = ins.opcode
            out_elems, out_bytes = _shape_elems_bytes(ins.shape)
            if op == "while":
                body = ins.attr("body")
                cond = ins.attr("condition")
                trip = self._trip_count(cond) if cond else 1
                if body:
                    total.add(self.cost(body), trip)
                if cond:
                    total.add(self.cost(cond), trip)
                continue
            if op in ("fusion", "call", "custom-call", "map", "reduce",
                      "reduce-window", "sort", "scatter", "select-and-scatter"):
                called = ins.attr("calls") or ins.attr("to_apply")
                if called and op in ("fusion", "call", "map"):
                    sub = self.cost(called)
                    total.flops += sub.flops
                    total.transcendentals += sub.transcendentals
                    # fusion internals don't touch HBM; bytes from this line
                    for k, v in sub.collectives.items():
                        st = total.collectives[k]
                        for f in ("count", "bytes", "wire_bytes"):
                            st[f] += v[f]
                elif op == "reduce":
                    total.flops += out_elems  # ~1 op per output elem per input
                op_bytes = out_bytes
                for i, o in enumerate(ins.operands()):
                    _, b = _shape_elems_bytes(symtab.get(o, ""))
                    if op == "fusion" and called:
                        # utilization: a parameter consumed only through
                        # slice/gather ops reads just the slices (the operand
                        # is often the full stacked-layers array)
                        sb = self._fused_param_bytes(called, i)
                        if sb is not None:
                            b = min(b, sb)
                    op_bytes += b
                total.bytes_accessed += op_bytes
                continue
            if op in _COLLECTIVES:
                base = op.replace("-start", "")
                s = self._group_size(ins.rest)
                # XLA:CPU float-normalization upcasts bf16 values to f32
                # before dots/collectives (host-platform artifact — on TPU
                # the payload stays bf16).  When the operand is a pure
                # upcast, count the original dtype's bytes.
                payload = out_bytes * self._upcast_factor(ins)
                if base == "all-reduce":
                    wire = 2.0 * (s - 1) / s * payload
                elif base in ("all-gather", "all-to-all"):
                    wire = (s - 1) / s * payload
                elif base == "reduce-scatter":
                    wire = float(s - 1) * payload
                else:
                    wire = float(payload)
                st = total.collectives[base]
                st["count"] += 1
                st["bytes"] += payload
                st["wire_bytes"] += wire
                total.bytes_accessed += payload
                continue
            if op in _SKIP_BYTES or op.endswith("-done"):
                continue
            # slicing ops touch only the slice, not the full operand (matches
            # HloCostAnalysis; critical inside scan bodies where the operand
            # is the full stacked-layers array)
            if op in ("dynamic-slice", "slice", "gather"):
                total.bytes_accessed += 2.0 * out_bytes
                continue
            if op in ("dynamic-update-slice", "scatter"):
                ops_ = ins.operands()
                upd = symtab.get(ops_[1], "") if len(ops_) > 1 else ""
                _, ub = _shape_elems_bytes(upd)
                total.bytes_accessed += 2.0 * ub + (ub if op == "scatter" else 0)
                continue
            # generic op: bytes = operands + output
            op_bytes = out_bytes
            for o in ins.operands():
                _, b = _shape_elems_bytes(symtab.get(o, ""))
                op_bytes += b
            total.bytes_accessed += op_bytes
            if op == "dot":
                total.flops += self._dot_flops(ins, symtab)
            elif op == "convolution":
                # approx: 2 x out x kernel elems (rare in this code base)
                total.flops += 2.0 * out_elems
            elif op in _TRANSCENDENTAL:
                total.transcendentals += out_elems
            elif op in _ELEMENTWISE:
                total.flops += out_elems
        return total

    def entry_cost(self) -> CostTotals:
        return self.cost(self.entry)


def top_instructions(hlo_text: str, n: int = 12) -> list[tuple]:
    """Largest trip-weighted byte consumers (debugging/perf-iteration aid).

    Returns [(bytes_total, 'loc: opcode name shape'), ...] descending.
    """
    model = HloCostModel(hlo_text)
    rows = []

    def walk(comp, mult):
        symtab = model._symtab(comp)
        for ins in model.comps.get(comp, []):
            op = ins.opcode
            if op == "while":
                body, cond = ins.attr("body"), ins.attr("condition")
                trip = model._trip_count(cond) if cond else 1
                if body:
                    walk(body, mult * trip)
                continue
            if op in _SKIP_BYTES or op.endswith("-done"):
                continue
            _, ob = _shape_elems_bytes(ins.shape)
            b = ob
            if op in ("dynamic-slice", "slice", "gather"):
                b = 2 * ob
            else:
                for i, o in enumerate(ins.operands()):
                    _, x = _shape_elems_bytes(symtab.get(o, ""))
                    if op == "fusion":
                        called = ins.attr("calls")
                        sb = model._fused_param_bytes(called, i) if called else None
                        if sb is not None:
                            x = min(x, sb)
                    b += x
            rows.append((b * mult,
                         f"{comp[:24]}: {op} {ins.name[:32]} {ins.shape[:48]} x{mult}"))

    walk(model.entry, 1)
    rows.sort(key=lambda r: -r[0])
    return rows[:n]


def analyze(hlo_text: str) -> dict:
    """Full trip-count-aware summary of a post-SPMD module (per device)."""
    model = HloCostModel(hlo_text)
    t = model.entry_cost()
    coll = {k: dict(v) for k, v in t.collectives.items()}
    return {
        "flops": t.flops,
        "transcendentals": t.transcendentals,
        "bytes_accessed": t.bytes_accessed,
        "per_op": coll,
        "collective_bytes": sum(v["bytes"] for v in coll.values()),
        "wire_bytes": sum(v["wire_bytes"] for v in coll.values()),
        "n_collectives": sum(v["count"] for v in coll.values()),
    }