"""Post-SPMD HLO text analyzer for roofline terms.

XLA's ``compiled.cost_analysis()`` visits a ``while`` body ONCE, so any
scan-over-layers model is undercounted by ~num_layers x (verified in
tests/test_hlo_analysis.py against unrolled references). This module parses
``compiled.as_text()`` directly:

  * builds a per-computation symbol table (op name -> shape/dtype),
  * computes dot FLOPs (batch/contracting-dim aware),
  * computes per-device HBM bytes (operands + results of top-level ops;
    fusion internals are free, matching HloCostAnalysis conventions),
  * computes collective wire bytes with group-size-aware formulas,
  * scales ``while`` bodies by their trip count (recovered from the loop
    condition's comparison constant) and recurses through fusions/calls.

All numbers are PER DEVICE (the input is the SPMD-partitioned module).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1, "u4": 1, "s4": 1,
}

COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


@dataclass
class Shape:
    dtype: str
    dims: Tuple[int, ...]

    @property
    def elems(self) -> int:
        n = 1
        for d in self.dims:
            n *= d
        return n

    @property
    def bytes(self) -> int:
        return self.elems * DTYPE_BYTES.get(self.dtype, 4)


def parse_type(text: str) -> List[Shape]:
    """Parse 'f32[4,8]{1,0}' or '(f32[2], bf16[3,4])' into Shape list."""
    shapes = []
    for m in _SHAPE_RE.finditer(text):
        dtype, dims = m.group(1), m.group(2)
        if dtype not in DTYPE_BYTES and dtype != "token":
            continue
        if dtype == "token":
            continue
        d = tuple(int(x) for x in dims.split(",")) if dims else ()
        shapes.append(Shape(dtype, d))
    return shapes


def type_bytes(text: str) -> int:
    return sum(s.bytes for s in parse_type(text))


@dataclass
class Op:
    name: str
    result_type: str
    opcode: str
    operands: List[str]
    attrs: str
    line: str


@dataclass
class Computation:
    name: str
    ops: Dict[str, Op] = field(default_factory=dict)
    order: List[str] = field(default_factory=list)


# one op per line:  %name = <type> opcode(%a, %b, ...), attr=..., ...
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*((?:\([^)]*\)|[\w\[\]{},\d]+))\s+"
    r"([\w\-]+)\((.*?)\)(.*)$"
)
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->\s*.*\{\s*$")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def parse_hlo(text: str) -> Tuple[Dict[str, Computation], Optional[str]]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry: Optional[str] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if not line:
            continue
        mc = _COMP_RE.match(line)
        if mc and ("{" in line):
            cur = Computation(mc.group(1))
            comps[cur.name] = cur
            if line.lstrip().startswith("ENTRY"):
                entry = cur.name
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        mo = _OP_RE.match(line)
        if not mo:
            continue
        name, rtype, opcode, args, attrs = mo.groups()
        operands = _OPERAND_RE.findall(args)
        op = Op(name, rtype, opcode, operands, attrs, line)
        cur.ops[name] = op
        cur.order.append(name)
    return comps, entry


def _group_size(attrs: str, line: str) -> int:
    # iota form: replica_groups=[G,S]<=...
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=", line)
    if m:
        return int(m.group(2))
    # explicit form: replica_groups={{0,1,2},{...}}
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[(\d+)\]<=", line)
    if m:  # single flat group
        return int(m.group(1))
    return 1


def _dot_flops(op: Op, table: Dict[str, str]) -> int:
    out = parse_type(op.result_type)
    if not out:
        return 0
    out_elems = out[0].elems
    lhs_type = table.get(op.operands[0]) if op.operands else None
    if lhs_type is None:
        return 2 * out_elems  # conservative
    lhs = parse_type(lhs_type)
    if not lhs:
        return 2 * out_elems
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.line)
    contract = 1
    if m and m.group(1):
        for d in m.group(1).split(","):
            i = int(d)
            if i < len(lhs[0].dims):
                contract *= lhs[0].dims[i]
    return 2 * out_elems * contract


@dataclass
class Cost:
    flops: float = 0.0
    bytes_accessed: float = 0.0
    collective_bytes: float = 0.0
    collective_counts: Dict[str, int] = field(default_factory=dict)
    collective_detail: Dict[str, float] = field(default_factory=dict)

    def add(self, other: "Cost", times: float = 1.0) -> None:
        self.flops += other.flops * times
        self.bytes_accessed += other.bytes_accessed * times
        self.collective_bytes += other.collective_bytes * times
        for k, v in other.collective_counts.items():
            self.collective_counts[k] = self.collective_counts.get(k, 0) + int(v * times)
        for k, v in other.collective_detail.items():
            self.collective_detail[k] = self.collective_detail.get(k, 0.0) + v * times


def _trip_count(cond: Computation) -> int:
    """Recover scan trip count from the loop condition's compare constant."""
    consts: Dict[str, int] = {}
    for op in cond.ops.values():
        if op.opcode == "constant":
            m = re.search(r"constant\((-?\d+)\)", op.line)
            if m:
                consts[op.name] = int(m.group(1))
    best = 0
    for op in cond.ops.values():
        if op.opcode == "compare":
            for o in op.operands:
                if o in consts:
                    best = max(best, consts[o])
        if op.opcode == "fusion":
            # compare may be fused; fall back to max constant in cond
            pass
    if best == 0 and consts:
        best = max(consts.values())
    return max(best, 1)


class HloCostModel:
    def __init__(self, text: str):
        self.comps, entry = parse_hlo(text)
        self._memo: Dict[str, Cost] = {}
        if entry is None:
            for name in self.comps:
                if name.startswith("main"):
                    entry = name
        if entry is None:
            # fallback: the computation with the most ops
            entry = max(self.comps, key=lambda n: len(self.comps[n].order))
        self.entry = entry

    # ------------------------------------------------------------------ #
    def computation_cost(self, name: str, bytes_at_callsite: bool = False) -> Cost:
        if name in self._memo:
            return self._memo[name]
        comp = self.comps.get(name)
        cost = Cost()
        if comp is None:
            return cost
        self._memo[name] = cost  # guard cycles
        table = {op.name: op.result_type for op in comp.ops.values()}

        for opn in comp.order:
            op = comp.ops[opn]
            oc = op.opcode
            if oc in ("parameter", "constant", "get-tuple-element", "tuple",
                      "bitcast", "after-all"):
                continue
            if oc == "while":
                body = re.search(r"body=%?([\w.\-]+)", op.line)
                cond = re.search(r"condition=%?([\w.\-]+)", op.line)
                trips = 1
                if cond and cond.group(1) in self.comps:
                    trips = _trip_count(self.comps[cond.group(1)])
                if body:
                    cost.add(self.computation_cost(body.group(1)), trips)
                continue
            if oc in ("call", "async-start"):
                m = re.search(r"(?:to_apply|calls)=%?([\w.\-]+)", op.line)
                if m:
                    cost.add(self.computation_cost(m.group(1)))
                continue
            if oc == "conditional":
                branches = re.findall(r"branch_computations=\{([^}]*)\}", op.line)
                if branches:
                    names = _OPERAND_RE.findall(branches[0])
                    if names:
                        cost.add(self.computation_cost(names[0]))
                continue

            # bytes: operands + result at this level
            ob = sum(type_bytes(table.get(o, "")) for o in op.operands)
            rb = type_bytes(op.result_type)
            cost.bytes_accessed += ob + rb

            if oc == "fusion":
                m = re.search(r"calls=%?([\w.\-]+)", op.line)
                if m:
                    inner = self.computation_cost(m.group(1))
                    cost.flops += inner.flops  # dots inside fusions
                continue
            if oc in ("dot", "convolution"):
                cost.flops += _dot_flops(op, table)
                continue
            kind = next((c for c in COLLECTIVES if oc == c or oc == c + "-start"), None)
            if oc.endswith("-done"):
                continue  # bytes/wire accounted at the -start op
            if kind is not None:
                g = _group_size(op.attrs, op.line)
                if kind == "all-reduce":
                    wire = 2 * rb * (g - 1) / max(g, 1)
                elif kind == "all-gather":
                    wire = rb * (g - 1) / max(g, 1)
                elif kind == "reduce-scatter":
                    wire = rb * (g - 1)
                elif kind == "all-to-all":
                    wire = rb * (g - 1) / max(g, 1)
                else:  # collective-permute
                    wire = rb
                cost.collective_bytes += wire
                cost.collective_counts[kind] = cost.collective_counts.get(kind, 0) + 1
                cost.collective_detail[kind] = cost.collective_detail.get(kind, 0.0) + wire
                continue
            # elementwise/reduce/etc: bytes already counted; flops ~ elems
            cost.flops += sum(s.elems for s in parse_type(op.result_type))
        return cost

    def entry_cost(self) -> Cost:
        return self.computation_cost(self.entry)


def analyze_text(text: str) -> Dict[str, float]:
    model = HloCostModel(text)
    c = model.entry_cost()
    return {
        "flops_per_device": c.flops,
        "bytes_per_device": c.bytes_accessed,
        "collective_bytes_per_device": c.collective_bytes,
        "collective_counts": dict(c.collective_counts),
        "collective_bytes_by_kind": dict(c.collective_detail),
    }
