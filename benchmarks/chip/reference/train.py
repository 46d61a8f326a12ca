"""The reference's first training steps: float32 parameters, the mean
gradient over the whole global batch, Adam as published (Kingma & Ba,
bias-corrected), and the learning-rate schedule the job states.

The moments live in host memory between steps and visit the device one
leaf at a time, so a model whose float32 parameters, gradients and two
moments would not fit beside each other on one chip still runs here.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import common as C


def lr_at(step: int, job: Dict) -> float:
    """Linear warm-up to ``lr`` over ``warmup`` steps, then cosine decay to
    0 at ``lr_horizon`` (step counts from 0)."""
    peak, warm, total = job["lr"], job["warmup"], job["lr_horizon"]
    if step < warm:
        return peak * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return 0.5 * peak * (1.0 + math.cos(math.pi * prog))


def leaf_norms(tree) -> List[float]:
    return [float(x) for x in jax.jit(lambda t: [
        jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
        for l in jax.tree.leaves(t)])(tree)]


def run_steps(nll_sum: Callable, sizes: Dict, make_params0: Callable,
              batches: Sequence,
              job: Dict, *, data_sharding=None, lowp=None,
              rows: slice = slice(None)) -> Dict[str, list]:
    """Train ``len(batches)`` steps from ``make_params0()`` (called once,
    so the caller keeps no copy on the device); returns the loss of
    each step, the per-leaf norm of the first gradient and the per-leaf
    norm of the parameters' change after the last step (leaf order of
    ``jax.tree.leaves``).  ``rows`` takes a slice of every batch (a fault
    planted for calibration); ``lowp`` rounds every matmul operand (the
    control)."""
    b1, b2, eps = job["adam_b1"], job["adam_b2"], job["adam_eps"]

    def loss_fn(p, tokens):
        total, count, aux = nll_sum(p, sizes, tokens)
        return total / count + aux

    def traced(p, tokens):
        with jax.default_matmul_precision("highest"):
            if lowp is None:
                return jax.value_and_grad(loss_fn)(p, tokens)
            with C.lower_precision(lowp):
                return jax.value_and_grad(loss_fn)(p, tokens)

    grad_fn = jax.jit(traced)

    @jax.jit
    def adam_leaf(p, g, m, v, lr, c1, c2):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        return p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps), m, v

    params = jax.tree.map(lambda a: a.astype(jnp.float32), make_params0())
    leaves0, treedef = jax.tree.flatten(params)
    start = [np.asarray(l) for l in leaves0]          # host copy of p0
    moments = [(np.zeros(l.shape, np.float32), np.zeros(l.shape, np.float32))
               for l in leaves0]
    del leaves0
    losses, g1 = [], None
    for t, batch in enumerate(batches):
        tokens = np.asarray(batch["tokens"])[rows]
        tokens = (jax.device_put(tokens, data_sharding) if data_sharding
                  is not None else jnp.asarray(tokens))
        loss, grads = grad_fn(params, tokens)
        losses.append(float(loss))
        if t == 0:
            g1 = leaf_norms(grads)
        lr = lr_at(t, job)
        c1, c2 = 1 - b1 ** (t + 1), 1 - b2 ** (t + 1)
        p_leaves = jax.tree.leaves(params)
        g_leaves = jax.tree.leaves(grads)
        del params, grads
        new = []
        for i, (p, g) in enumerate(zip(p_leaves, g_leaves)):
            m, v = moments[i]
            p, m, v = adam_leaf(p, g, m, v, lr, c1, c2)
            moments[i] = (np.asarray(m), np.asarray(v))
            new.append(p)
            p_leaves[i] = g_leaves[i] = None
        params = jax.tree.unflatten(treedef, new)
        del new
    deltas = [float(np.linalg.norm((np.asarray(p) - s).ravel()))
              for p, s in zip(jax.tree.leaves(params), start)]
    return {"losses": losses, "g1": g1, "d3": deltas}
