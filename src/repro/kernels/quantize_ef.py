"""Fused error-feedback + int8 quantization Pallas kernels (survey §3.2.1).

One HBM->VMEM pass per (8·128-aligned) tile computes

    corrected = g + e                      (error feedback, Eq. 2)
    scale     = max|corrected| per tile
    q         = round(corrected / scale · 127)  -> int8 payload
    e_new     = corrected - q · scale / 127     (residual)

The GPU formulation is three kernels (EF add, max-reduce, quantize) with
three HBM round-trips; on TPU we tile so each block's scale is computed in
VMEM and everything is written once (DESIGN.md §5/§11).  Per-TILE scales
(vs per-tensor) are the TPU-friendly choice and also tighten the
quantization error; the wire format is (int8[tile], f32 scale per tile).

The flat buffer is viewed as (ntiles, tile) rows and a grid step takes a
block of rows; each row's scale lands in a (rows, 1) column block.  Both
block shapes meet the TPU's (8, 128) rule — a one-element scale block
per tile does not, and the TPU compiler refuses it.

Non-tile-multiple lengths are zero-padded to the next tile boundary and
the outputs sliced back: appended zeros cannot raise a tile's max|·|
scale, cannot win a top-k bisection round against any non-zero value, and
quantize to q=0 with e_new=0 — so the partial tile's scale and residual
are exactly what ``ref.py`` computes (pinned by the ragged parity tests).

The decode side is ``dequant_accum_pallas``: unpack + accumulate of all
gathered payloads in ONE pass per output tile (the gather-pattern wire
reads each payload once and writes the dense sum once — the one-read /
one-write contract of DESIGN.md §11).

``interpret=None`` (the default) resolves via ``dispatch.resolve_interpret``:
compiled on TPU, interpreter elsewhere.  Callers must not hardcode it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import resolve_interpret

TILE = 8 * 128  # VPU-aligned flat tile
BLOCK_ELEMS = 256 * 1024  # elements per operand block (1 MiB of f32)


def _pad_to_tile(x, tile: int):
    """Zero-pad a flat array to the next tile multiple (no-op if aligned)."""
    n = x.shape[0]
    m = -(-n // tile) * tile
    if m != n:
        x = jnp.pad(x, (0, m - n))
    return x


def _block_rows(ntiles: int, tile: int, ranks: int = 1) -> int:
    """Tiles per grid step: about ``BLOCK_ELEMS`` elements per operand,
    a multiple of 8 (the sublane count) or all tiles when fewer.  A
    partial last block is fine: tiles are independent rows, and the
    rows past ``ntiles`` are never written back."""
    rows = max(8, BLOCK_ELEMS // (tile * ranks) // 8 * 8)
    return ntiles if ntiles <= rows else rows


def _row_spec(rows: int, width: int):
    return pl.BlockSpec((rows, width), lambda i: (i, 0))


def _kernel(g_ref, e_ref, q_ref, e_new_ref, scale_ref, *, decay: float):
    # one row per tile: (rows, tile) blocks, (rows, 1) scales
    g = g_ref[...].astype(jnp.float32)
    e = e_ref[...].astype(jnp.float32)
    corrected = g + decay * e
    scale = jnp.maximum(jnp.max(jnp.abs(corrected), axis=1, keepdims=True),
                        1e-30)
    q = jnp.clip(jnp.round(corrected / scale * 127.0), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    e_new_ref[...] = corrected - q * (scale / 127.0)
    scale_ref[...] = scale


def quantize_ef_pallas(g, e, *, decay: float = 1.0, tile: int = TILE,
                       interpret=None):
    """g, e: flat (n,) arrays, any length (zero-padded to a tile multiple
    internally).  Returns (q int8 (n,), e_new f32 (n,),
    scales f32 (ceil(n/tile),))."""
    interpret = resolve_interpret(interpret)
    n = g.shape[0]
    g = _pad_to_tile(g, tile)
    e = _pad_to_tile(e, tile)
    ntiles = g.shape[0] // tile
    rows = _block_rows(ntiles, tile)
    kernel = functools.partial(_kernel, decay=decay)
    q, e_new, scales = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(ntiles, rows),),
        in_specs=[_row_spec(rows, tile), _row_spec(rows, tile)],
        out_specs=[_row_spec(rows, tile), _row_spec(rows, tile),
                   _row_spec(rows, 1)],
        out_shape=[jax.ShapeDtypeStruct((ntiles, tile), jnp.int8),
                   jax.ShapeDtypeStruct((ntiles, tile), jnp.float32),
                   jax.ShapeDtypeStruct((ntiles, 1), jnp.float32)],
        name="quantize_ef",
        interpret=interpret,
    )(g.reshape(ntiles, tile), e.reshape(ntiles, tile))
    return (q.reshape(-1)[:n], e_new.reshape(-1)[:n], scales.reshape(-1))


def _q_kernel(x_ref, q_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True), 1e-30)
    q = jnp.clip(jnp.round(x / scale * 127.0), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    scale_ref[...] = scale


def quantize_pallas(x, *, tile: int = TILE, interpret=None):
    """Per-tile int8 quantization WITHOUT error feedback — the per-hop
    requantization step of the compressed ring (``collectives/ring_fused``).
    x: flat (n,), any length.  Returns (q int8 (n,), scales (ceil(n/tile),))."""
    interpret = resolve_interpret(interpret)
    n = x.shape[0]
    x = _pad_to_tile(x, tile)
    ntiles = x.shape[0] // tile
    rows = _block_rows(ntiles, tile)
    q, scales = pl.pallas_call(
        _q_kernel,
        grid=(pl.cdiv(ntiles, rows),),
        in_specs=[_row_spec(rows, tile)],
        out_specs=[_row_spec(rows, tile), _row_spec(rows, 1)],
        out_shape=[jax.ShapeDtypeStruct((ntiles, tile), jnp.int8),
                   jax.ShapeDtypeStruct((ntiles, 1), jnp.float32)],
        name="quantize_tiles",
        interpret=interpret,
    )(x.reshape(ntiles, tile))
    return q.reshape(-1)[:n], scales.reshape(-1)


def _accum_kernel(q_ref, s_ref, out_ref):
    # q_ref: (w, rows, tile) int8, s_ref: (w, rows, 1) f32 — all ranks
    q = q_ref[...].astype(jnp.float32)
    out_ref[...] = jnp.sum(q * (s_ref[...] / 127.0), axis=0)


def dequant_accum_pallas(q, scales, *, tile: int = TILE, interpret=None):
    """Fused dequantize + accumulate: the decode side of the gathered int8
    wire.  q: (w, n) int8 payloads from w ranks, scales: (w, ceil(n/tile))
    f32.  Returns the (n,) f32 SUM of the dequantized payloads — each
    payload element is read once and the dense sum written once."""
    interpret = resolve_interpret(interpret)
    w, n = q.shape
    ntiles = -(-n // tile)
    m = ntiles * tile
    assert scales.shape == (w, ntiles), (scales.shape, (w, ntiles))
    if m != n:
        # per-rank pads: one (w, n) int8 pad takes the TPU compiler ~20 s
        q = jnp.stack([_pad_to_tile(r, tile) for r in q])
    rows = _block_rows(ntiles, tile, ranks=w)
    out = pl.pallas_call(
        _accum_kernel,
        grid=(pl.cdiv(ntiles, rows),),
        in_specs=[pl.BlockSpec((w, rows, tile), lambda i: (0, i, 0)),
                  pl.BlockSpec((w, rows, 1), lambda i: (0, i, 0))],
        out_specs=_row_spec(rows, tile),
        out_shape=jax.ShapeDtypeStruct((ntiles, tile), jnp.float32),
        name="dequant_accum",
        interpret=interpret,
    )(q.reshape(w, ntiles, tile), scales.reshape(w, ntiles, 1))
    return out.reshape(-1)[:n]


def dequantize(q, scales, tile: int = TILE):
    n = q.shape[0]
    s = jnp.repeat(scales, tile)[:n]
    return q.astype(jnp.float32) * (s / 127.0)
