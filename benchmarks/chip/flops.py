"""Operations a training step requires, per token, from a configuration's
stated sizes (the top-level keys of its file under ``configs/``).  Each
family's forward count is ``forward_flops(sizes, seq)`` in its plain
reference, ``reference/<family>.py``, beside the equations it counts.

forward = 2 x (matmul parameters a token passes through) + one
sequence-mixing term per layer; training = 3 x forward (the backward
pass costs two forwards).  Counted as the model requires them:

  * matmul parameters include the output head and every projection; the
    embedding lookup is no matmul and is left out, as are norm scales and
    biases; routed experts count at ``top_k / n_experts`` of their weights;
  * causal attention and MLA: q.k and p.v over the mean causal context
    ``(T + 1) / 2``.

Nothing recomputed in the backward pass, no capacity padding, no
attention over masked positions and no vocabulary padding is counted.
"""
from __future__ import annotations

import importlib
from typing import Dict


def train_flops_per_token(sizes: Dict, family: str, seq: int) -> float:
    """Forward plus backward operations per trained token; the forward
    count is ``forward_flops`` of the family's module under
    ``reference/``."""
    module = importlib.import_module(f"reference.{family}")
    return 3.0 * module.forward_flops(sizes, seq)
