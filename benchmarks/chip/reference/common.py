"""Plain float32 pieces shared by the reference models.

Nothing here imports the system under test.  Every matrix product goes
through :func:`mm` / :func:`einsum`, which run at ``Precision.HIGHEST`` in
float32.  The control (the step below the precision the configuration
states) computes every such product in ``LOWP`` instead: both operands
rounded in the forward pass, and the cotangent rounded in the backward
pass, the backward products then taking the rounded forward operands.
``LOWP`` is set only by :func:`lower_precision`.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LOWP: Optional[jnp.dtype] = None


@contextlib.contextmanager
def lower_precision(dtype):
    """Compute every matmul in ``dtype`` while tracing (the control)."""
    global LOWP
    old, LOWP = LOWP, dtype
    try:
        yield
    finally:
        LOWP = old


def _round(x, dtype):
    """x rounded to ``dtype``: a float format of 16 bits by a cast; int8
    and 8-bit floats with one scale per tensor, which maps its largest
    magnitude to the format's largest value."""
    dt = jnp.dtype(dtype)
    if dt.itemsize > 1:
        return x.astype(dt).astype(x.dtype)
    top = float(jnp.iinfo(dt).max if jnp.issubdtype(dt, jnp.integer)
                else jnp.finfo(dt).max)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / top, 1.0)
    y = x / scale
    y = (jnp.clip(jnp.round(y), -top, top) if jnp.issubdtype(dt, jnp.integer)
         else y.astype(dt).astype(x.dtype))
    return y * scale


def _lowp(fn, dtype, *xs):
    """``fn`` (bilinear) of its operands rounded to ``dtype``, with a
    backward pass that rounds the cotangent too."""
    @jax.custom_vjp
    def f(*args):
        return fn(*(_round(a, dtype) for a in args))

    def fwd(*args):
        q = tuple(_round(a, dtype) for a in args)
        return fn(*q), q

    def bwd(q, g):
        return jax.vjp(fn, *q)[1](_round(g, dtype))

    f.defvjp(fwd, bwd)
    return f(*xs)


def mm(x, w):
    fn = functools.partial(jnp.matmul, precision=HIGHEST)
    return fn(x, w) if LOWP is None else _lowp(fn, LOWP, x, w)


def einsum(spec, *xs):
    fn = functools.partial(jnp.einsum, spec, precision=HIGHEST)
    return fn(*xs) if LOWP is None else _lowp(fn, LOWP, *xs)


def rmsnorm(x, scale, eps):
    """RMSNorm whose stored scale is a delta on 1 (zero at init)."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def rope(x, positions, theta):
    """Rotary embedding on the last dim of x (..., T, H, d), halves layout."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None].astype(jnp.float32) * freqs          # (T, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def next_token_nll(h, table, tokens, chunk=512):
    """Summed next-token cross-entropy of hidden states h (B, T, d) against
    the output table (V, d), and the number of predicted positions.  The
    last position predicts nothing.  Logits are formed ``chunk`` positions
    at a time and recomputed in the backward pass."""
    B, T, _ = h.shape
    labels = jnp.concatenate([tokens[:, 1:], -jnp.ones_like(tokens[:, :1])], 1)
    c = min(chunk, T)
    assert T % c == 0, (T, c)

    @jax.checkpoint
    def piece(hc, lc):
        logits = einsum("btd,vd->btv", hc, table)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, jnp.maximum(lc, 0)[..., None],
                                   axis=-1)[..., 0]
        return jnp.sum((logz - gold) * (lc >= 0))

    hs = h.reshape(B, T // c, c, -1).swapaxes(0, 1)
    ls = labels.reshape(B, T // c, c).swapaxes(0, 1)
    total = jax.lax.map(lambda a: piece(*a), (hs, ls)).sum()
    return total, jnp.asarray(B * (T - 1), jnp.float32)
