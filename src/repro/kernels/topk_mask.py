"""Block-local top-k sparsification Pallas kernels (survey §3.2.2).

Exact global top-k needs a full sort across HBM — hostile to the TPU memory
hierarchy.  Following DGC's sampled-threshold argument, each VMEM tile keeps
its own top ceil(k·tile/n) elements, found by BISECTING a threshold on |x|
inside the tile (``iters`` rounds of compare+popcount, no sort, fully
vectorized on the VPU).  The deviation from exact per-tile top-k is bounded
by the bisection resolution (2^-iters · max|x|) and tested against the
exact oracle.

``topk_ef_pallas`` is the fused hot-path variant: the error-feedback add,
the bisection mask, and the residual update happen in ONE pass —

    corrected = g + decay · e
    y         = corrected where kept, else 0     (the payload)
    e_new     = corrected where dropped, else 0  (the residual)

so a top-k bucket reads g and e once and writes y and e_new once
(DESIGN.md §11).  Ragged lengths are zero-padded to the tile boundary:
a zero pad entry can never beat a non-zero threshold in the bisection
(|0| >= mid is false for mid > 0), and in an all-zero tile it contributes
y = e_new = 0 either way, so sliced outputs match ``ref.py`` exactly.

``interpret=None`` resolves via ``dispatch.resolve_interpret`` (compiled
on TPU, interpreter elsewhere) — callers must not hardcode it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import resolve_interpret
from repro.kernels.quantize_ef import _pad_to_tile

TILE = 8 * 128


def _bisect_threshold(ax, k: int, iters: int):
    """Shared bisection: the threshold ``hi`` such that |x| >= hi keeps
    (approximately) the top-k entries of one tile."""
    hi = jnp.max(ax)
    lo = jnp.zeros_like(hi)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((ax >= mid).astype(jnp.int32))
        # too many kept -> raise threshold
        return jnp.where(cnt > k, mid, lo), jnp.where(cnt > k, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return hi


def _kernel(x_ref, y_ref, *, k: int, iters: int):
    x = x_ref[...].astype(jnp.float32)
    ax = jnp.abs(x)
    hi = _bisect_threshold(ax, k, iters)
    y_ref[...] = jnp.where(ax >= hi, x, 0.0).astype(y_ref.dtype)


def topk_mask_pallas(x, *, ratio: float = 0.01, tile: int = TILE,
                     iters: int = 16, interpret=None):
    """x: flat (n,), any length (zero-padded to a tile multiple).  Returns
    x with all but the (approximately) top ratio·tile entries per tile
    zeroed."""
    interpret = resolve_interpret(interpret)
    n = x.shape[0]
    x = _pad_to_tile(x, tile)
    m = x.shape[0]
    k = max(1, int(tile * ratio))
    kernel = functools.partial(_kernel, k=k, iters=iters)
    out = pl.pallas_call(
        kernel,
        grid=(m // tile,),
        in_specs=[pl.BlockSpec((tile,), lambda i: (i,))],
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((m,), x.dtype),
        name="topk_mask",
        interpret=interpret,
    )(x)
    return out[:n]


def _ef_kernel(g_ref, e_ref, y_ref, e_new_ref, *, k: int, iters: int,
               decay: float):
    g = g_ref[...].astype(jnp.float32)
    e = e_ref[...].astype(jnp.float32)
    corrected = g + decay * e
    ax = jnp.abs(corrected)
    hi = _bisect_threshold(ax, k, iters)
    keep = ax >= hi
    y_ref[...] = jnp.where(keep, corrected, 0.0)
    e_new_ref[...] = jnp.where(keep, 0.0, corrected)


def topk_ef_pallas(g, e, *, ratio: float = 0.01, tile: int = TILE,
                   iters: int = 16, decay: float = 1.0, interpret=None):
    """Fused EF + top-k mask + residual: g, e flat (n,), any length.
    Returns (y f32 (n,), e_new f32 (n,)) with y + e_new == g + decay·e."""
    interpret = resolve_interpret(interpret)
    n = g.shape[0]
    g = _pad_to_tile(g, tile)
    e = _pad_to_tile(e, tile)
    m = g.shape[0]
    k = max(1, int(tile * ratio))
    kernel = functools.partial(_ef_kernel, k=k, iters=iters, decay=decay)
    y, e_new = pl.pallas_call(
        kernel,
        grid=(m // tile,),
        in_specs=[pl.BlockSpec((tile,), lambda i: (i,)),
                  pl.BlockSpec((tile,), lambda i: (i,))],
        out_specs=[pl.BlockSpec((tile,), lambda i: (i,)),
                   pl.BlockSpec((tile,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((m,), jnp.float32),
                   jax.ShapeDtypeStruct((m,), jnp.float32)],
        name="topk_ef",
        interpret=interpret,
    )(g, e)
    return y[:n], e_new[:n]
