"""Trip-count-aware HLO cost analysis for the dry-run roofline.

XLA's ``compiled.cost_analysis()`` counts every while-loop body ONCE
(verified in tests/test_hlo_cost.py) — useless for scan-over-layers
programs where 88 iterations of the body are the whole model.  This
module walks the post-optimization HLO text and accounts:

  * FLOPs       — dots (2·M·N·K from the dot dims), elementwise arith,
                  reduces/transcendentals; fusions cost their called
                  computation; while loops cost trip_count × body.
  * HBM bytes   — post-fusion traffic model: every fusion/instruction
                  reads its operands and writes its result once
                  (parameters/constants inside fusions are not re-counted).
  * collectives — per-op on-wire bytes with ring formulas, replica-group
                  aware: all-reduce 2(S-1)/S·b, all-gather/reduce-scatter/
                  all-to-all (S-1)/S·b_full, collective-permute b.

Trip counts are recovered from scan/fori while-conditions (the compare-
against-constant in the condition computation), which covers every loop
this framework emits (lax.scan / fori_loop / microbatch accumulation).

Costs are PER DEVICE (the HLO is the SPMD-partitioned per-device module).
"""

from __future__ import annotations

import math
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[\w\[\],\s{}/*#=.\-]+?\)?)\s+"
    r"([\w\-]+)\((.*)$")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_REPLICA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_REPLICA_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_TRIP_RE = re.compile(r"known_trip_count[^0-9]*?(\d+)")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_BRANCH_RE = re.compile(
    r"(?:true_computation|false_computation|branch_computations)="
    r"\{?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")

ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "and", "or", "xor", "not", "select", "compare", "clamp",
    "floor", "ceil", "round-nearest-afz", "sign", "convert", "power",
    "remainder", "shift-left", "shift-right-logical",
    "shift-right-arithmetic", "atan2",
}
TRANSCENDENTAL = {"exponential", "log", "tanh", "rsqrt", "sqrt", "logistic",
                  "sine", "cosine", "exponential-minus-one", "log-plus-one",
                  "erf", "cbrt"}
COLLECTIVES = {"all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "all-reduce-start", "all-gather-start",
               "collective-permute-start"}


def _parse_shape(text: str) -> Tuple[int, int]:
    """-> (elements, bytes), summing tuple shapes."""
    elems = 0
    byts = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        elems += n
        byts += n * DTYPE_BYTES[dt]
    return elems, byts


@dataclass
class Instr:
    name: str
    shape_text: str
    opcode: str
    rest: str
    elems: int
    bytes_out: int


@dataclass
class Computation:
    name: str
    instrs: List[Instr] = field(default_factory=list)


@dataclass
class CostTotals:
    flops: float = 0.0
    transcendentals: float = 0.0
    hbm_bytes: float = 0.0
    collective_wire_bytes: float = 0.0
    collective_counts: Dict[str, int] = field(default_factory=lambda:
                                              defaultdict(int))
    collective_bytes_by_op: Dict[str, float] = field(
        default_factory=lambda: defaultdict(float))

    def add(self, other: "CostTotals", times: float = 1.0):
        self.flops += other.flops * times
        self.transcendentals += other.transcendentals * times
        self.hbm_bytes += other.hbm_bytes * times
        self.collective_wire_bytes += other.collective_wire_bytes * times
        for k, v in other.collective_counts.items():
            self.collective_counts[k] += int(v * times)
        for k, v in other.collective_bytes_by_op.items():
            self.collective_bytes_by_op[k] += v * times


def parse_hlo(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry = None
    for line in text.splitlines():
        if line.rstrip().endswith("{") and ("->" in line or
                                            line.startswith("ENTRY")):
            m = _COMP_HDR_RE.match(line.strip())
            name = None
            if m:
                name = m.group(1)
            else:
                toks = line.strip().split()
                for t in toks:
                    if t.startswith("%") or t.startswith("ENTRY"):
                        continue
                    name = t.strip("%(").split("(")[0]
                    break
            cur = Computation(name=name or f"comp{len(comps)}")
            comps[cur.name] = cur
            if line.startswith("ENTRY"):
                entry = cur.name
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, shape_text, opcode, rest = m.groups()
        elems, byts = _parse_shape(shape_text)
        cur.instrs.append(Instr(name, shape_text, opcode, rest, elems, byts))
    if entry:
        comps["__entry__"] = comps[entry]
    return comps


def _dot_flops(instr: Instr, shapes: Dict[str, Tuple[int, int]]) -> float:
    """dot flops = 2 x result_elems x contraction size."""
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", instr.rest)
    ops = re.findall(r"%([\w.\-]+)", instr.rest)
    lhs_shape_m = re.search(r"(\w+)\[([\d,]*)\]", instr.rest)
    k = None
    if m and lhs_shape_m is None and ops:
        pass
    # parse lhs operand shape from the operand defs we tracked
    if ops:
        lhs = ops[0]
        dims = shapes.get(lhs)
        if dims and m:
            cdims = [int(x) for x in m.group(1).split(",") if x]
            k = 1
            for c in cdims:
                if c < len(dims):
                    k *= dims[c]
    if k is None:
        k = 1
    return 2.0 * instr.elems * k


class HloCostModel:
    def __init__(self, text: str):
        self.comps = parse_hlo(text)
        self._dims: Dict[str, Dict[str, List[int]]] = {}
        self._memo: Dict[str, CostTotals] = {}
        self._trip_memo: Dict[str, int] = {}
        self._build_dims(text)

    # track full dim lists per instruction name, per computation
    def _build_dims(self, text: str):
        cur = None
        for line in text.splitlines():
            if line.rstrip().endswith("{") and ("->" in line
                                                or line.startswith("ENTRY")):
                m = _COMP_HDR_RE.match(line.strip())
                cur = m.group(1) if m else None
                self._dims[cur] = {}
                # parameters in header
                for pm in re.finditer(r"%?([\w.\-]+):\s*(\w+)\[([\d,]*)\]",
                                      line):
                    nm, dt, dims = pm.groups()
                    if dt in DTYPE_BYTES:
                        self._dims[cur][nm] = [int(x) for x in
                                               dims.split(",") if x]
                continue
            if cur is None:
                continue
            m = _INSTR_RE.match(line)
            if m:
                name, shape_text = m.group(1), m.group(2)
                sm = _SHAPE_RE.search(shape_text)
                if sm:
                    self._dims[cur][name] = [int(x) for x in
                                             sm.group(2).split(",") if x]

    def trip_count(self, cond_name: str) -> int:
        """Max integer constant in the loop condition (scan trip count)."""
        if cond_name in self._trip_memo:
            return self._trip_memo[cond_name]
        comp = self.comps.get(cond_name)
        best = 1
        if comp:
            for ins in comp.instrs:
                for c in re.finditer(r"constant\((\d+)\)", ins.rest):
                    best = max(best, int(c.group(1)))
                if ins.opcode == "constant":
                    c = re.search(r"\((\d+)\)", ins.rest)
                    if c:
                        best = max(best, int(c.group(1)))
        self._trip_memo[cond_name] = best
        return best

    def _collective_cost(self, ins: Instr, totals: CostTotals):
        op = ins.opcode.replace("-start", "")
        groups = _REPLICA_RE.search(ins.rest)
        if groups:
            size = int(groups.group(2))
        else:
            lst = _REPLICA_LIST_RE.search(ins.rest)
            size = len(lst.group(1).split(",")) if lst else 2
        size = max(size, 1)
        b = float(ins.bytes_out)
        if op == "all-reduce":
            wire = 2.0 * (size - 1) / size * b
        elif op == "all-gather":
            wire = (size - 1) / size * b            # result is the full gather
        elif op == "reduce-scatter":
            wire = (size - 1) * b                    # result is the shard
        elif op == "all-to-all":
            wire = (size - 1) / size * b
        else:  # collective-permute
            wire = b
        totals.collective_wire_bytes += wire
        totals.collective_counts[op] += 1
        totals.collective_bytes_by_op[op] += wire

    def comp_cost(self, name: str) -> CostTotals:
        if name in self._memo:
            return self._memo[name]
        totals = CostTotals()
        comp = self.comps.get(name)
        if comp is None:
            return totals
        self._memo[name] = totals  # break cycles
        dims = self._dims.get(name, {})
        for ins in comp.instrs:
            op = ins.opcode
            if op == "while":
                body = _BODY_RE.search(ins.rest)
                cond = _COND_RE.search(ins.rest)
                bc = _TRIP_RE.search(ins.rest)
                if bc:
                    trips = int(bc.group(1))        # XLA known_trip_count
                elif cond:
                    trips = self.trip_count(cond.group(1))
                else:
                    trips = 1
                if body:
                    totals.add(self.comp_cost(body.group(1)), times=trips)
                    totals.add(self.comp_cost(cond.group(1)), times=trips)
                continue
            if op == "fusion":
                called = _CALLS_RE.search(ins.rest)
                out_bytes = float(ins.bytes_out)
                if called:
                    sub = self.comp_cost(called.group(1))
                    # flops from the fused body; bytes = fusion boundary IO
                    totals.flops += sub.flops
                    totals.transcendentals += sub.transcendentals
                    totals.collective_wire_bytes += sub.collective_wire_bytes
                    # in-place carry updates: a fusion rooted at
                    # dynamic-update-slice aliases its operand — XLA writes
                    # only the updated region, so charge the update, not
                    # the full carry (otherwise scan carries look like
                    # full-array traffic every iteration).
                    upd = self._dus_update_bytes(called.group(1))
                    if upd is not None:
                        # aliased in-place update: the big carry operand
                        # never round-trips HBM; charge only the update.
                        totals.hbm_bytes += upd
                        continue
                totals.hbm_bytes += out_bytes + self._operand_bytes(ins, dims)
                continue
            if op in ("call", "conditional", "custom-call"):
                called = _CALLS_RE.search(ins.rest)
                if called:
                    totals.add(self.comp_cost(called.group(1)))
                branches = _BRANCH_RE.search(ins.rest)
                if branches:
                    names = [x.strip().lstrip("%")
                             for x in branches.group(1).split(",")]
                    if op == "conditional" and names:
                        # cost a conditional as its most expensive branch
                        best = None
                        for nm in names:
                            c = self.comp_cost(nm)
                            if best is None or c.flops > best.flops:
                                best = c
                        if best is not None:
                            totals.add(best)
                totals.hbm_bytes += ins.bytes_out
                continue
            if op in COLLECTIVES:
                self._collective_cost(ins, totals)
                totals.hbm_bytes += 2 * ins.bytes_out
                continue
            if op == "dot":
                totals.flops += _dot_flops(ins, dims)
                totals.hbm_bytes += ins.bytes_out + self._operand_bytes(ins,
                                                                        dims)
                continue
            if op in ELEMENTWISE:
                totals.flops += ins.elems
                continue
            if op in TRANSCENDENTAL:
                totals.flops += ins.elems
                totals.transcendentals += ins.elems
                continue
            if op in ("reduce", "reduce-window"):
                totals.flops += self._operand_elems(ins, dims)
                totals.hbm_bytes += ins.bytes_out + self._operand_bytes(ins,
                                                                        dims)
                continue
            if op == "dynamic-update-slice":
                # aliased in-place update: traffic = the update operand
                ops_names = re.findall(r"%([\w.\-]+)", ins.rest)
                upd = dims.get(ops_names[1]) if len(ops_names) > 1 else None
                if upd is not None:
                    totals.hbm_bytes += 2.0 * 4.0 * math.prod(upd)
                else:
                    totals.hbm_bytes += ins.bytes_out
                continue
            if op in ("copy", "transpose", "reshape", "broadcast", "slice",
                      "dynamic-slice", "concatenate",
                      "gather", "scatter", "pad", "iota", "reverse",
                      "copy-start", "copy-done", "bitcast"):
                totals.hbm_bytes += ins.bytes_out
                continue
            # parameters/constants/tuples: free
        return totals

    def _dus_update_bytes(self, comp_name: str) -> Optional[float]:
        """If ``comp_name`` contains a dynamic-update-slice (scan-carry
        in-place update, possibly convert-wrapped), the bytes of its update
        operand (read+write of the touched region), else None.  Models the
        TPU in-place DUS-fusion path (aliased output; only the updated
        region hits HBM)."""
        comp = self.comps.get(comp_name)
        if not comp or not comp.instrs:
            return None
        dims = self._dims.get(comp_name, {})
        for ins in comp.instrs:
            if ins.opcode != "dynamic-update-slice":
                continue
            ops_names = re.findall(r"%([\w.\-]+)", ins.rest)
            if len(ops_names) >= 2 and dims.get(ops_names[1]) is not None:
                upd = dims[ops_names[1]]
                return 2.0 * 4.0 * math.prod(upd) if upd else 8.0
            return float(ins.bytes_out)
        return None

    def _operand_bytes(self, ins: Instr, dims: Dict[str, List[int]]) -> float:
        total = 0.0
        for opn in re.findall(r"%([\w.\-]+)", ins.rest.split("),")[0]):
            d = dims.get(opn)
            if d is not None:
                total += 4.0 * math.prod(d) if d else 4.0
        return total

    def _operand_elems(self, ins: Instr, dims: Dict[str, List[int]]) -> float:
        total = 0.0
        for opn in re.findall(r"%([\w.\-]+)", ins.rest.split("),")[0]):
            d = dims.get(opn)
            if d is not None:
                total += float(math.prod(d)) if d else 1.0
        return total

    def entry_cost(self) -> CostTotals:
        return self.comp_cost(self.comps["__entry__"].name) \
            if "__entry__" in self.comps else CostTotals()


def analyze(compiled_text: str) -> CostTotals:
    """Per-device totals for a compiled (post-SPMD) HLO module text."""
    return HloCostModel(compiled_text).entry_cost()
