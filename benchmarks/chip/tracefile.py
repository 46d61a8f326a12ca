"""From a profiler trace to the numbers the per-layer metrics read.

``load(path)`` turns the profiler's ``.xplane.pb`` into a small plain
structure, ``Trace``: for every device plane its operations as
``(name, start_ns, end_ns)`` from the ``XLA Ops`` line, and the
benchmark's own host spans (names starting ``bench.``).  A TPU trace
names an op by its whole HLO instruction text; it is cut to the
instruction's name (``fusion.185``, ``while.264``), and a compiled
Pallas kernel's name gets the prefix ``tpu_custom_call:``.  A ``while``
op spans its body's ops, so op times may nest: busy time is a union.  Everything
below works on that structure, so a recorded trace can be kept as JSON
and the reduction tested without a chip.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[str, int, int]
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
KERNEL_PREFIX = "tpu_custom_call:"


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Interval]]     # plane -> ops, sorted by start
    spans: List[Interval]                  # host spans, sorted by start
    # plane -> its busy intervals in the window: a trace of millions of
    # ops is read by several metrics, and each would sort it again
    _busy: Dict[str, List[Tuple[int, int]]] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    def to_json(self) -> dict:
        return {"devices": self.devices, "spans": self.spans}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        devs = {k: [tuple(e) for e in v] for k, v in d["devices"].items()}
        return cls(devs, [tuple(e) for e in d["spans"]])


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((op_name(e.name), int(e.start_ns),
                                int(e.end_ns)) for e in line.events)
            devices[plane.name] = sorted(ops, key=lambda e: e[1])
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, int(e.start_ns), int(e.end_ns)))
    return Trace(devices, sorted(spans, key=lambda e: e[1]))


def op_name(text: str) -> str:
    """``%fusion.185 = (bf16[...]) fusion(...)`` -> ``fusion.185``."""
    name = text.split(" = ", 1)[0].lstrip("%")
    return KERNEL_PREFIX + name if "tpu_custom_call" in text else name


def save_json(trace: Trace, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace.to_json(), f)


def load_json(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        return Trace.from_json(json.load(f))


# -- interval arithmetic ------------------------------------------------------

def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of the (disjoint, sorted) intervals ``a`` not covered by
    the (disjoint, sorted) intervals ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# -- the window and the steps ---------------------------------------------------

def spans_named(trace: Trace, name: str) -> List[Tuple[int, int]]:
    return [(s, e) for n, s, e in trace.spans if n == name]


def window(trace: Trace) -> Tuple[int, int]:
    w = spans_named(trace, "bench.window")
    if len(w) != 1:
        raise ValueError(f"expected one bench.window span, found {len(w)}")
    return w[0]


def busy(trace: Trace, plane: str) -> List[Tuple[int, int]]:
    if plane not in trace._busy:
        lo, hi = window(trace)
        trace._busy[plane] = union(clip(
            [(s, e) for _, s, e in trace.devices[plane]], lo, hi))
    return trace._busy[plane]


def busiest(trace: Trace) -> str:
    return max(trace.devices, key=lambda p: total(busy(trace, p)))


def step_gaps_ns(trace: Trace, plane: str) -> List[int]:
    """Device idle time between consecutive steps: from the last op that
    started inside one ``bench.step`` span to the first op that started
    inside the next."""
    steps = spans_named(trace, "bench.step")
    ops = trace.devices[plane]
    firsts, lasts = [], []
    for s, e in steps:
        inside = [(a, b) for _, a, b in ops if s <= a < e]
        if not inside:
            return []
        firsts.append(min(a for a, _ in inside))
        lasts.append(max(b for _, b in inside))
    return [max(0, firsts[i + 1] - lasts[i]) for i in range(len(steps) - 1)]


def idle_gaps(trace: Trace, plane: str, top: int = 10):
    """The longest device-idle gaps inside the window, each named by the
    innermost host span that covers its midpoint (``host`` when none)."""
    lo, hi = window(trace)
    gaps = subtract([(lo, hi)], busy(trace, plane))
    named = []
    for s, e in gaps:
        mid = (s + e) // 2
        cover = [(n, a, b) for n, a, b in trace.spans if a <= mid < b
                 and n != "bench.window"]
        name = min(cover, key=lambda c: c[2] - c[1])[0] if cover else "host"
        named.append((name, (e - s) / 1e9))
    return sorted(named, key=lambda g: -g[1])[:top]


def self_times(ops: Sequence[Interval]) -> List[Tuple[str, int]]:
    """Each op's time less the time of the ops nested inside it (a
    ``while`` op holds its body's ops)."""
    out: List[Tuple[str, int]] = []
    stack: List[list] = []                 # [name, start, end, child time]

    def close(item):
        out.append((item[0], item[2] - item[1] - item[3]))

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0])
    for item in reversed(stack):
        close(item)
    return out


def top_ops(trace: Trace, plane: str, top: int = 10):
    """The ops with the most self time inside the window, summed by name."""
    lo, hi = window(trace)
    tot: Dict[str, int] = {}
    inside = [(n, max(s, lo), min(e, hi)) for n, s, e in trace.devices[plane]
              if e > lo and s < hi]
    for n, t in self_times(inside):
        tot[n] = tot.get(n, 0) + t
    return sorted(((n, t / 1e9) for n, t in tot.items()),
                  key=lambda x: -x[1])[:top]


def step_starts_ns(trace: Trace, plane: str) -> List[int]:
    """Start of the first device op inside each ``bench.step`` span."""
    ops = trace.devices[plane]
    starts = []
    for s, e in spans_named(trace, "bench.step"):
        inside = [a for _, a, _ in ops if s <= a < e]
        if inside:
            starts.append(min(inside))
    return starts

