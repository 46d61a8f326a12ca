"""Pallas TPU flash attention with sliding-window and logit-softcap support.

Schedule: grid (batch*kv_heads*group, num_q_blocks, num_kv_blocks); the last
grid dimension is sequential ("arbitrary"), carrying the running softmax
(m, l, acc) in VMEM scratch across kv blocks — the streaming form of
models/attention.flash_attention, with BlockSpecs pinning one (q_blk, hd)
query tile and one (kv_blk, hd) key/value tile in VMEM per step.  MXU
alignment: q_blk/kv_blk multiples of 128 at production shapes (tests sweep
smaller, unaligned-but-valid tile sizes too); hd is the lane dimension.

The pure-jnp oracle is ``repro.kernels.ref.flash_attention_ref``; on CPU the
kernel runs with interpret=True (correctness), on TPU compiled.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            softcap: Optional[float], window: Optional[int], causal: bool,
            kv_blk: int, nk: int, scale: float):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)            # (q_blk, hd)
    k = k_ref[0].astype(jnp.float32)            # (kv_blk, hd)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    q_blk = q.shape[0]
    q_pos = qi * q_blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ki * kv_blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
        if not causal:
            mask &= (k_pos - q_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           q_blk: int = 128, kv_blk: int = 128,
                           interpret: bool = True):
    """q: (B, T, H, hd); k, v: (B, S, KV, hd), H = KV * G.
    Returns (B, T, H, hd)."""
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    q_blk = min(q_blk, T)
    kv_blk = min(kv_blk, S)
    assert T % q_blk == 0 and S % kv_blk == 0
    nq, nk = T // q_blk, S // kv_blk
    scale = 1.0 / np.sqrt(hd)

    # (B*KV*G, T, hd) query layout; kv broadcast across the group
    qr = q.reshape(B, T, KV, G, hd).transpose(0, 2, 3, 1, 4).reshape(
        B * KV * G, T, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)

    grid = (B * KV * G, nq, nk)
    kernel = functools.partial(_kernel, softcap=softcap, window=window,
                               causal=causal, kv_blk=kv_blk, nk=nk, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_blk, hd), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, kv_blk, hd), lambda b, qi, ki, G=G: (b // G, ki, 0)),
            pl.BlockSpec((1, kv_blk, hd), lambda b, qi, ki, G=G: (b // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_blk, hd), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV * G, T, hd), q.dtype),
        scratch_shapes=[
            # running softmax state lives across the sequential kv dimension
            pltpu.VMEM((q_blk, 1), jnp.float32),
            pltpu.VMEM((q_blk, 1), jnp.float32),
            pltpu.VMEM((q_blk, hd), jnp.float32),
        ],
        name="flash_attention",
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, KV, G, T, hd).transpose(0, 3, 1, 2, 4).reshape(
        B, T, H, hd)
