"""The numbers that decide ``correct``, and their comparison with limits.

Three numbers compare the program's first training steps with the
reference's, from the same weights and batches:

  * ``loss_gap``: the largest relative gap of a step's loss;
  * ``grad_gap``: over leaves, the largest gap between the norm of the
    first gradient as the optimizer received it and the reference's
    norm of that leaf's mean gradient, over the larger of that reference
    norm and the median leaf's;
  * ``delta_gap``: the same for the norm of each leaf's change after the
    checked steps.  Leaves whose reference gradient is under
    ``EXCLUDE`` of the median leaf's move by round-off alone under Adam
    and are left out.

A limit of ``null`` in a cell's limits file means the number is read and
printed but not compared.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

EXCLUDE = 1e-3


def leaf_gap(prog: Sequence[float], ref: Sequence[float],
             keep: Optional[Sequence[bool]] = None) -> Tuple[float, int]:
    """(largest gap, leaf index) of per-leaf norms, each gap over the
    larger of the reference norm and the median reference norm."""
    idx = [i for i in range(len(ref)) if keep is None or keep[i]]
    med = statistics.median(ref[i] for i in idx)
    worst, at = 0.0, -1
    for i in idx:
        gap = abs(prog[i] - ref[i]) / max(ref[i], med)
        if not math.isfinite(prog[i]):
            gap = math.inf
        if gap > worst or at < 0:
            worst, at = gap, i
    return worst, at


def readings(prog: Dict, ref: Dict) -> Dict[str, Tuple[float, int]]:
    """prog/ref: {"losses": [...], "g1": [...], "d3": [...]} with per-leaf
    norms in one leaf order.  Returns name -> (value, where)."""
    loss = [(abs(a - b) / abs(b) if math.isfinite(a) else math.inf)
            for a, b in zip(prog["losses"], ref["losses"])]
    g_med = statistics.median(ref["g1"])
    keep = [g >= EXCLUDE * g_med for g in ref["g1"]]
    return {
        "loss_gap": (max(loss), loss.index(max(loss))),
        "grad_gap": leaf_gap(prog["g1"], ref["g1"]),
        "delta_gap": leaf_gap(prog["d3"], ref["d3"], keep),
    }


def judge(values: Dict[str, float], limits: Dict[str, Optional[float]]
          ) -> Tuple[bool, List[Tuple[str, float, float]]]:
    """(all within limits, [(name, value, limit)] for each compared number)."""
    rows = [(k, values[k], lim) for k, lim in limits.items()
            if lim is not None and k in values]
    ok = all(math.isfinite(v) and v <= lim for _, v, lim in rows)
    return ok, rows
