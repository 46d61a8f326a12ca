"""Initial weights from ``--seed``, made on the device in one jitted call.

The benchmark makes the weights itself, for the parameter tree the
program declares (its leaf paths, shapes and dtypes), so that the
program and the reference start from the same values and the reference
takes nothing the program computed.  Norm scales and biases start at 0;
embedding and output tables, routers, gate projections, the mLSTM
convolution and sLSTM recurrent weights at standard deviation 0.02; every
other matrix at ``1 / sqrt(fan_in)``.
"""
from __future__ import annotations

from typing import Any, Callable, List

import jax
import jax.numpy as jnp

ZERO = ("scale", "conv_b", "b_if", "b")
SMALL = ("table", "router", "conv_w", "w_if", "r")


def _name(path) -> str:
    keys = [p.key for p in path if hasattr(p, "key")]
    return str(keys[-1]) if keys else ""


def init_std(path, shape) -> float:
    name = _name(path)
    if name in ZERO:
        return 0.0
    if name in SMALL:
        return 0.02
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    return float(fan_in) ** -0.5


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31),
                              (seed >> 31) % 2 ** 32)


def maker(abstract) -> Callable[[Any], Any]:
    """``make(key)`` -> a parameter tree shaped like ``abstract`` (a tree of
    ShapeDtypeStructs), traceable, so it can be jitted or inlined."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def make(key):
        leaves: List[jnp.ndarray] = []
        for i, (path, sds) in enumerate(flat):
            std = init_std(path, sds.shape)
            if std == 0.0:
                leaves.append(jnp.zeros(sds.shape, sds.dtype))
            else:
                x = jax.random.normal(jax.random.fold_in(key, i), sds.shape,
                                      jnp.float32) * std
                leaves.append(x.astype(sds.dtype))
        return jax.tree.unflatten(treedef, leaves)

    return make


def leaf_names(abstract) -> List[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(abstract)
    return [jax.tree_util.keystr(p) for p, _ in flat]
