"""Roofline-term extraction from compiled (post-SPMD-partitioning) HLO text.

``compiled.cost_analysis()`` counts each ``while`` body ONCE, which
structurally undercounts programs that scan over layers/time (all of ours).
This module re-derives the three roofline inputs from the HLO text itself:

  * matmul FLOPs   — every ``dot`` op: 2 · |out| · (contracted dims),
  * collective bytes — all-reduce / all-gather / reduce-scatter /
    all-to-all / collective-permute operand (and wire) bytes,
  * loop correction — ops inside ``while`` bodies are multiplied by the trip
    count parsed from the loop condition's comparison constant, propagated
    through the call graph (fusions, nested loops).

Cross-checked in tests against ``cost_analysis()`` on unrolled programs and
against the analytic per-arch calculator (launch/analytic.py).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(.*->.*\{\s*$")
_CALL_ATTR_RE = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_LAYOUT_RE = re.compile(r"\{[^{}]*\}")
_OPCODE_RE = re.compile(r"\s([a-z][\w\-]*)\(")

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                  "collective-permute")


def _parse_shape(text: str) -> Tuple[Optional[str], int]:
    """First 'dtype[a,b,c]' in text -> (dtype, numel). Tuples: sum handled
    by callers via parse_all_shapes."""
    m = _SHAPE_RE.search(text)
    if not m:
        return None, 0
    dt, dims = m.group(1), m.group(2)
    if dt not in _DTYPE_BYTES:
        return None, 0
    numel = 1
    for d in dims.split(","):
        if d:
            numel *= int(d)
    return dt, numel


def _shape_bytes(text: str) -> int:
    dt, numel = _parse_shape(text)
    return numel * _DTYPE_BYTES.get(dt, 0) if dt else 0


@dataclasses.dataclass
class Instruction:
    name: str
    line: str
    op: str


@dataclasses.dataclass
class Computation:
    name: str
    instructions: List[Instruction]
    # name -> full def text (for operand shape lookup)
    defs: Dict[str, str]


def parse_hlo(text: str) -> Tuple[Dict[str, Computation], str]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry = ""
    for raw in text.splitlines():
        line = raw.rstrip()
        mc = _COMP_RE.match(line)
        if mc and line.endswith("{"):
            cur = Computation(mc.group(1), [], {})
            comps[cur.name] = cur
            if line.startswith("ENTRY"):
                entry = cur.name
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        md = _DEF_RE.match(line)
        if not md:
            continue
        name, rhs = md.group(1), md.group(2)
        # the opcode: the first word before '(' once the layouts, whose
        # tiles read like calls ("{1,0:T(8,128)}"), are taken out
        mo = _OPCODE_RE.search(" " + _LAYOUT_RE.sub("", rhs))
        op = mo.group(1) if mo else ""
        cur.defs[name] = rhs
        cur.instructions.append(Instruction(name, rhs, op))
    return comps, entry


def _trip_count(comps: Dict[str, Computation], cond_name: str) -> int:
    comp = comps.get(cond_name)
    if not comp:
        return 1
    consts = []
    for ins in comp.instructions:
        consts += [int(x) for x in _CONST_RE.findall(ins.line)]
    return max(consts) if consts else 1


def _dot_flops(comp: Computation, ins: Instruction) -> int:
    out_dt, out_numel = _parse_shape(ins.line)
    if out_numel == 0:
        return 0
    mdims = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.line)
    if not mdims:
        return 0
    cdims = [int(x) for x in mdims.group(1).split(",") if x]
    # lhs operand shape
    ops = _OPERAND_RE.findall(ins.line.split("dot(", 1)[1])
    if not ops:
        return 0
    lhs_def = comp.defs.get(ops[0], "")
    m = _SHAPE_RE.search(lhs_def if lhs_def else "")
    if not m:
        return 0
    dims = [int(x) for x in m.group(2).split(",") if x]
    contracted = 1
    for c in cdims:
        if c < len(dims):
            contracted *= dims[c]
    return 2 * out_numel * contracted


def _group_size(line: str, total_devices: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip()])
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    return total_devices


def _collective_bytes(comp: Computation, ins: Instruction,
                      total_devices: int) -> Tuple[int, float]:
    """Returns (operand_bytes, wire_bytes_per_chip)."""
    inner = ins.line.split(ins.op + "(", 1)
    operands = _OPERAND_RE.findall(inner[1].split(")")[0]) if len(inner) > 1 else []
    op_bytes = 0
    for o in operands:
        d = comp.defs.get(o)
        if d:
            op_bytes += _shape_bytes(d)
    p = max(_group_size(ins.line, total_devices), 1)
    if ins.op == "all-reduce":
        wire = 2.0 * op_bytes * (p - 1) / p
    elif ins.op == "all-gather":
        wire = float(op_bytes) * (p - 1)
    elif ins.op in ("reduce-scatter", "all-to-all"):
        wire = float(op_bytes) * (p - 1) / p
    else:  # collective-permute
        wire = float(op_bytes)
    return op_bytes, wire


@dataclasses.dataclass
class HLOStats:
    dot_flops: float = 0.0
    collective_operand_bytes: float = 0.0
    collective_wire_bytes: float = 0.0
    memory_bytes: float = 0.0          # operand+output bytes of top-level ops
    collective_counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    while_trip_counts: List[int] = dataclasses.field(default_factory=list)


_SKIP_MEM_OPS = {"tuple", "get-tuple-element", "parameter", "constant",
                 "bitcast", "copy", "after-all", "partition-id", "replica-id",
                 "while", "conditional", "call"}


def _instruction_mem_bytes(comp: "Computation", ins: "Instruction") -> int:
    """HLO bytes-accessed approximation: output bytes + operand bytes, with
    fusions counted as one op (their internals never touch HBM).  Control /
    aliasing ops are skipped."""
    if ins.op in _SKIP_MEM_OPS or not ins.op:
        return 0
    total = _shape_bytes(ins.line)
    args = ins.line.split(ins.op + "(", 1)
    if len(args) > 1:
        for o in _OPERAND_RE.findall(args[1].split(")")[0]):
            d = comp.defs.get(o)
            if d:
                total += _shape_bytes(d)
    return total


def analyze(text: str, total_devices: int = 1) -> HLOStats:
    comps, entry = parse_hlo(text)
    stats = HLOStats()
    seen_while: List[int] = []

    def walk(comp_name: str, mult: float, stack: Tuple[str, ...]):
        comp = comps.get(comp_name)
        if comp is None or comp_name in stack:
            return
        stack = stack + (comp_name,)
        for ins in comp.instructions:
            stats.memory_bytes += mult * _instruction_mem_bytes(comp, ins)
            if ins.op == "dot":
                stats.dot_flops += mult * _dot_flops(comp, ins)
            elif ins.op in COLLECTIVE_OPS:
                ob, wb = _collective_bytes(comp, ins, total_devices)
                stats.collective_operand_bytes += mult * ob
                stats.collective_wire_bytes += mult * wb
                stats.collective_counts[ins.op] = (
                    stats.collective_counts.get(ins.op, 0) + int(mult))
            if ins.op == "while":
                mb = re.search(r"body=%?([\w.\-]+)", ins.line)
                mcnd = re.search(r"condition=%?([\w.\-]+)", ins.line)
                trips = _trip_count(comps, mcnd.group(1)) if mcnd else 1
                seen_while.append(trips)
                if mb:
                    walk(mb.group(1), mult * max(trips, 1), stack)
            else:
                for callee in _CALL_ATTR_RE.findall(ins.line):
                    if "condition" in ins.line and callee in ins.line.split("condition=")[-1]:
                        continue
                    walk(callee, mult, stack)
    walk(entry, 1.0, ())
    stats.while_trip_counts = seen_while
    return stats


# ---------------------------------------------------------------------------
# Overlap of a scheduled program's collectives with its compute
# ---------------------------------------------------------------------------

_COMPUTE_OPS = ("fusion", "convolution", "custom-call")
_CHAIN_RE = re.compile(r'chain_id="([^"]*)"')
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_FUSION_CALL_RE = re.compile(r"calls=%?([\w.\-]+)")


def _is_collective(op: str) -> bool:
    return op.startswith(COLLECTIVE_OPS)


def exchange_overlap(text: str, scope: str = "grad_sync") -> Optional[float]:
    """Share of the bytes of the collectives under the named scope ``scope``
    that a scheduled HLO module (``compiled.as_text()``) overlaps with
    compute; None when no collective runs under that scope.

    A collective is asynchronous when its exchange spans several
    instructions of one schedule (an entry or loop body): an
    ``all-reduce-start`` and the ``all-reduce-done`` that consumes it, or the
    TPU's async collective fusions, whose start, compute and done fusions
    each hold the same collective under one ``chain_id``.  It overlaps when
    at least one compute instruction (a fusion, convolution or custom call)
    is scheduled between its first and last instruction.  A synchronous
    collective overlaps nothing.  Bytes are the collective's operand bytes,
    each instruction of the text counted once."""
    comps, _ = parse_hlo(text)
    fused = {m.group(1) for c in comps.values() for ins in c.instructions
             if ins.op == "fusion"
             for m in [_FUSION_CALL_RE.search(ins.line)] if m}
    held: Dict[str, List[Tuple[Computation, Instruction]]] = {}

    def holds(name: str) -> List[Tuple[Computation, Instruction]]:
        """The collectives of a fused computation, nested fusions too."""
        if name not in held:
            held[name] = []
            comp = comps.get(name)
            for ins in comp.instructions if comp else ():
                if _is_collective(ins.op):
                    held[name].append((comp, ins))
                elif ins.op == "fusion":
                    m = _FUSION_CALL_RE.search(ins.line)
                    held[name] += holds(m.group(1)) if m else []
        return held[name]

    total = overlapped = 0.0
    for cname, comp in comps.items():
        if cname in fused:
            continue
        spans: Dict[str, List[int]] = {}
        sizes: Dict[str, int] = {}
        for i, ins in enumerate(comp.instructions):
            if _is_collective(ins.op):
                members = [(comp, ins)]
            elif ins.op == "fusion":
                m = _FUSION_CALL_RE.search(ins.line)
                members = holds(m.group(1)) if m else []
            else:
                continue
            for owner, c in members:
                op_name = _OP_NAME_RE.search(c.line)
                if not op_name or scope not in op_name.group(1):
                    continue
                chain = _CHAIN_RE.search(c.line)
                if chain:
                    key = "chain " + chain.group(1)
                elif c.op.endswith("-done"):
                    ops = _OPERAND_RE.findall(c.line.split(c.op + "(", 1)[1])
                    key = ops[0] if ops else c.name
                else:
                    key = c.name
                spans.setdefault(key, []).append(i)
                if key not in sizes and not c.op.endswith("-done"):
                    sizes[key] = _collective_bytes(owner, c, 1)[0]
        for key, at in spans.items():
            nbytes = sizes.get(key, 0)
            total += nbytes
            if any(comp.instructions[j].op in _COMPUTE_OPS
                   for j in range(min(at) + 1, max(at))):
                overlapped += nbytes
    return overlapped / total if total else None
