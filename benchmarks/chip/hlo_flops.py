"""Matrix-product operations of a compiled program, from its HLO text, with
each ``while`` body counted once per trip.

``compiled.cost_analysis()`` counts a loop body once, so it undercounts a
step that scans over layers or time.  Here every ``dot`` counts
``2 |out| (contracted size)`` and every ``convolution`` (a TPU program
lowers its matmuls to them) ``2 |out|`` times its multiply-adds per
output element;
a computation called from a loop is multiplied by the loop's trip count
(``known_trip_count`` where the compiler states it, else the largest
integer constant of the loop's condition).  Element-wise work is not
counted, so this is a floor on what the program computes.
"""
from __future__ import annotations

import json
import re
from typing import Dict, List, Tuple

import numpy as np

_COMP = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_DEF = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP = re.compile(r"\s([\w\-]+)\(")
_CALLS = re.compile(r"(?:calls|to_apply|body)=%([\w.\-]+)")
_TRIP = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_CONST = re.compile(r"s32\[\](?:\{[^}]*\})?\s+constant\((\d+)\)")


def _dims(text: str) -> List[int]:
    m = _SHAPE.search(text)
    return [int(x) for x in m.group(2).split(",") if x] if m else []


def _numel(dims: List[int]) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def parse(text: str) -> Tuple[Dict[str, List[Tuple[str, str, str]]], str]:
    """computation -> [(name, op, right-hand side)], and the entry's name."""
    comps: Dict[str, List[Tuple[str, str, str]]] = {}
    cur, entry = None, ""
    for line in text.splitlines():
        mc = _COMP.match(line)
        if mc:
            cur = comps.setdefault(mc.group(1), [])
            if line.startswith("ENTRY"):
                entry = mc.group(1)
            continue
        if line.strip() == "}":
            cur = None
            continue
        md = _DEF.match(line) if cur is not None else None
        if md:
            mo = _OP.search(" " + md.group(2).split(" metadata=")[0])
            cur.append((md.group(1), mo.group(1) if mo else "", md.group(2)))
    return comps, entry


def _operands(rhs: str, op: str) -> List[str]:
    inner = rhs.split(op + "(", 1)[1].split(")", 1)[0]
    return re.findall(r"%([\w.\-]+)", inner)


def op_flops(rhs: str, op: str, shapes: Dict[str, str]) -> float:
    out = _numel(_dims(rhs))
    ops = _operands(rhs, op)
    if op == "dot":
        m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", rhs)
        lhs = _dims(shapes.get(ops[0], ""))
        k = _numel([lhs[int(i)] for i in m.group(1).split(",") if i]) \
            if m and lhs else 0
        return 2.0 * out * k
    if op == "convolution" and len(ops) > 1:
        return 2.0 * out * _conv_macs(rhs, _dims(shapes.get(ops[0], "")),
                                      _dims(shapes.get(ops[1], "")))
    return 0.0


def _window(rhs: str, key: str, n: int, default: str) -> List[str]:
    m = re.search(r"window=\{[^}]*\b" + key + r"=([\w_]+)", rhs)
    return m.group(1).split("x") if m else [default] * n


def _conv_macs(rhs: str, lhs: List[int], ker: List[int]) -> float:
    """Multiply-adds per output element of a convolution: the kernel's
    input features times, for each spatial dimension, the mean number of
    window taps that land on a real input.  A TPU program writes a batched
    matmul as a convolution whose spatial window spans the batch with the
    input dilated by it, so only one tap in a window is real."""
    m = re.search(r"dim_labels=(\w+)_(\w+)->(\w+)", rhs)
    if not m or not lhs or not ker:
        return 0.0
    lhs_l, ker_l, out_l = m.groups()
    out = _dims(rhs)
    macs = float(ker[ker_l.index("i")])
    spatial = sorted(c for c in ker_l if c.isdigit())
    n = len(spatial)
    stride = _window(rhs, "stride", n, "1")
    pad = _window(rhs, "pad", n, "0_0")
    l_dil = _window(rhs, "lhs_dilate", n, "1")
    r_dil = _window(rhs, "rhs_dilate", n, "1")
    for j, c in enumerate(spatial):
        n_in, k, n_out = lhs[lhs_l.index(c)], ker[ker_l.index(c)], \
            out[out_l.index(c)]
        ld = int(l_dil[j])
        x = (np.arange(n_out)[:, None] * int(stride[j])
             + np.arange(k)[None, :] * int(r_dil[j])
             - int(pad[j].split("_")[0]))
        real = (x >= 0) & (x <= (n_in - 1) * ld) & (x % ld == 0)
        macs *= real.sum() / n_out
    return macs


def _trips(comps, line: str) -> int:
    m = _TRIP.search(line)
    if m:
        return int(m.group(1))
    cond = re.search(r"condition=%([\w.\-]+)", line)
    consts = [int(c) for _, _, rhs in comps.get(cond.group(1) if cond else "",
                                                [])
              for c in _CONST.findall(rhs)]
    return max(consts) if consts else 1


def matmul_flops(text: str) -> float:
    comps, entry = parse(text)

    def walk(name: str, stack: Tuple[str, ...]) -> float:
        body = comps.get(name)
        if body is None or name in stack:
            return 0.0
        shapes = {n: rhs for n, _, rhs in body}
        total = 0.0
        for _, op, rhs in body:
            total += op_flops(rhs, op, shapes)
            mult = _trips(comps, rhs) if op == "while" else 1
            for callee in _CALLS.findall(rhs):
                total += mult * walk(callee, stack + (name,))
        return total

    return walk(entry, ())


def trip_counts(text: str) -> List[int]:
    comps, _ = parse(text)
    return [_trips(comps, rhs) for body in comps.values()
            for _, op, rhs in body if op == "while"]


if __name__ == "__main__":
    import sys
    with open(sys.argv[1]) as f:
        t = f.read()
    print(json.dumps({"matmul_flops": matmul_flops(t),
                      "while_trips": trip_counts(t)}))
