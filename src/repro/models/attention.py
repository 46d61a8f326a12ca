"""Attention: GQA/MQA with RoPE, sliding windows, logit softcap, QK-norm,
DeepSeek-V2 MLA (latent KV), and single-token KV-cache decoding.

Full-sequence attention is computed in a chunked, flash-style streaming form
(``lax.scan`` over query and key blocks with a running softmax) so that the
32k prefill shapes never materialize a (T, T) score matrix.  The Pallas TPU
kernel in ``repro.kernels.flash_attention`` implements the same schedule with
explicit VMEM BlockSpecs; this module is its pure-jnp twin and the fallback
used on CPU and in dry-runs.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LayerSpec, ModelConfig
from repro.models.layers import ParamDesc, apply_rope, norm_desc, rmsnorm
from repro.models.sharding_ctx import constrain

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Chunked (flash-style) full-sequence attention
# ---------------------------------------------------------------------------

def _block_mask(q_pos, k_pos, window):
    m = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= (q_pos[:, None] - k_pos[None, :]) < window
    return m


def _masked_scores(qc, kc, qp, kp, scale, softcap, causal, window):
    """(B,KV,G,cq,hd) x (B,KV,ck,hd) -> capped+masked scores (f32)."""
    s = jnp.einsum("bkgqh,bkch->bkgqc", qc, kc,
                   preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    if causal:
        mask = _block_mask(qp, kp, window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    elif window is not None:
        mask = jnp.abs(qp[:, None] - kp[None, :]) < window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    return s


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(qg, kg, vg, causal, window, softcap, q_chunk, kv_chunk, q_offset):
    out, _ = _flash_fwd_impl(qg, kg, vg, causal, window, softcap,
                             q_chunk, kv_chunk, q_offset)
    return out


def _flash_fwd_impl(qg, kg, vg, causal, window, softcap, q_chunk, kv_chunk,
                    q_offset):
    """qg: (B,KV,G,T,hd); kg/vg: (B,KV,S,hd). Returns (out, lse)."""
    B, KV, G, T, hd = qg.shape
    S = kg.shape[2]
    scale = 1.0 / np.sqrt(hd)
    nq, nk = T // q_chunk, S // kv_chunk
    q_positions = q_offset + jnp.arange(T)
    k_positions = jnp.arange(S)

    def q_step(_, qi):
        qc = jax.lax.dynamic_slice_in_dim(qg, qi * q_chunk, q_chunk, axis=3)
        qp = jax.lax.dynamic_slice_in_dim(q_positions, qi * q_chunk, q_chunk, 0)

        def kv_step(carry, ki):
            m_prev, l_prev, acc = carry
            kc = jax.lax.dynamic_slice_in_dim(kg, ki * kv_chunk, kv_chunk, axis=2)
            vc = jax.lax.dynamic_slice_in_dim(vg, ki * kv_chunk, kv_chunk, axis=2)
            kp = jax.lax.dynamic_slice_in_dim(k_positions, ki * kv_chunk, kv_chunk, 0)
            s = _masked_scores(qc, kc, qp, kp, scale, softcap, causal, window)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqc,bkch->bkgqh", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc), None

        init = (jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32),
                jnp.zeros((B, KV, G, q_chunk), jnp.float32),
                jnp.zeros((B, KV, G, q_chunk, hd), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(kv_step, init, jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return None, (out.astype(qg.dtype), lse)

    _, (chunks, lses) = jax.lax.scan(q_step, None, jnp.arange(nq))
    # chunks: (nq, B, KV, G, cq, hd) -> (B, KV, G, T, hd)
    out = jnp.moveaxis(chunks, 0, 3).reshape(B, KV, G, T, hd)
    lse = jnp.moveaxis(lses, 0, 3).reshape(B, KV, G, T)
    return out, lse


def _flash_fwd(qg, kg, vg, causal, window, softcap, q_chunk, kv_chunk, q_offset):
    out, lse = _flash_fwd_impl(qg, kg, vg, causal, window, softcap,
                               q_chunk, kv_chunk, q_offset)
    return out, (qg, kg, vg, out, lse)


def _flash_bwd(causal, window, softcap, q_chunk, kv_chunk, q_offset,
               res, do):
    """FlashAttention-2 style backward: recompute P per (q, kv) block from
    the saved log-sum-exp; memory is O(block), not O(T^2) and no per-step
    probability residuals are stored."""
    qg, kg, vg, out, lse = res
    B, KV, G, T, hd = qg.shape
    S = kg.shape[2]
    scale = 1.0 / np.sqrt(hd)
    nq, nk = T // q_chunk, S // kv_chunk
    q_positions = q_offset + jnp.arange(T)
    k_positions = jnp.arange(S)
    do = do.astype(jnp.float32)
    delta = jnp.sum(do * out.astype(jnp.float32), axis=-1)   # (B,KV,G,T)

    def kv_step(carry, ki):
        dq = carry
        kc = jax.lax.dynamic_slice_in_dim(kg, ki * kv_chunk, kv_chunk, axis=2)
        vc = jax.lax.dynamic_slice_in_dim(vg, ki * kv_chunk, kv_chunk, axis=2)
        kp = jax.lax.dynamic_slice_in_dim(k_positions, ki * kv_chunk, kv_chunk, 0)

        def q_step(carry_q, qi):
            dk, dv = carry_q
            qc = jax.lax.dynamic_slice_in_dim(qg, qi * q_chunk, q_chunk, axis=3)
            qp = jax.lax.dynamic_slice_in_dim(q_positions, qi * q_chunk, q_chunk, 0)
            lse_c = jax.lax.dynamic_slice_in_dim(lse, qi * q_chunk, q_chunk, axis=3)
            do_c = jax.lax.dynamic_slice_in_dim(do, qi * q_chunk, q_chunk, axis=3)
            dl_c = jax.lax.dynamic_slice_in_dim(delta, qi * q_chunk, q_chunk, axis=3)
            s_raw = jnp.einsum("bkgqh,bkch->bkgqc", qc, kc,
                               preferred_element_type=jnp.float32) * scale
            if softcap is not None:
                t = jnp.tanh(s_raw / softcap)
                s = softcap * t
            else:
                s = s_raw
            if causal:
                mask = _block_mask(qp, kp, window)[None, None, None]
            elif window is not None:
                mask = (jnp.abs(qp[:, None] - kp[None, :]) < window)[None, None, None]
            else:
                mask = jnp.ones(s.shape[-2:], jnp.bool_)[None, None, None]
            p = jnp.where(mask, jnp.exp(s - lse_c[..., None]), 0.0)
            dv = dv + jnp.einsum("bkgqc,bkgqh->bkch", p, do_c)
            dp = jnp.einsum("bkgqh,bkch->bkgqc", do_c, vc.astype(jnp.float32))
            ds = p * (dp - dl_c[..., None])
            if softcap is not None:
                ds = ds * (1.0 - t * t)
            ds = ds * scale
            dq_c = jnp.einsum("bkgqc,bkch->bkgqh", ds, kc.astype(jnp.float32))
            dk = dk + jnp.einsum("bkgqc,bkgqh->bkch", ds, qc.astype(jnp.float32))
            return (dk, dv), dq_c

        init = (jnp.zeros((B, KV, kv_chunk, hd), jnp.float32),
                jnp.zeros((B, KV, kv_chunk, hd), jnp.float32))
        (dk, dv), dq_chunks = jax.lax.scan(q_step, init, jnp.arange(nq))
        dq_new = jnp.moveaxis(dq_chunks, 0, 3).reshape(B, KV, G, T, hd)
        return dq + dq_new, (dk, dv)

    dq0 = jnp.zeros((B, KV, G, T, hd), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(kv_step, dq0, jnp.arange(nk))
    dk = jnp.moveaxis(dks, 0, 2).reshape(B, KV, S, hd)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(B, KV, S, hd)
    return dq.astype(qg.dtype), dk.astype(kg.dtype), dv.astype(vg.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None, q_chunk: int = 512,
                    kv_chunk: int = 1024, q_offset: int = 0):
    """q: (B, T, H, hd); k, v: (B, S, KV, hd) with H = KV * G.

    Returns (B, T, H, hd).  Streaming softmax over (q, kv) blocks — the
    score matrix is never materialized — with a FlashAttention-2 custom VJP
    (backward recomputes probabilities per block from the saved LSE, so
    training memory is O(T·hd) instead of O(T·S)).  ``q_offset`` is the
    absolute position of q[0].
    """
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    q_chunk = min(q_chunk, T)
    kv_chunk = min(kv_chunk, S)
    assert T % q_chunk == 0 and S % kv_chunk == 0, (T, S, q_chunk, kv_chunk)

    # (B, KV, G, T, hd) so grouped heads broadcast against (B, KV, S, hd)
    qg = constrain(q.reshape(B, T, KV, G, hd).transpose(0, 2, 3, 1, 4),
                   ("b", "m", None, None, None))
    kg = constrain(k.transpose(0, 2, 1, 3), ("b", "m", None, None))
    vg = constrain(v.transpose(0, 2, 1, 3), ("b", "m", None, None))
    out = _flash(qg, kg, vg, causal, window, softcap, q_chunk, kv_chunk,
                 q_offset)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, hd)


def attention_reference(q, k, v, *, causal=True, window=None, softcap=None,
                        q_offset: int = 0):
    """Naive O(T^2)-memory oracle (tests only)."""
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, T, KV, G, hd)
    s = jnp.einsum("btkgh,bskh->bkgts", qg, k,
                   preferred_element_type=jnp.float32) / np.sqrt(hd)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qp = q_offset + jnp.arange(T)
    kp = jnp.arange(S)
    if causal:
        s = jnp.where(_block_mask(qp, kp, window)[None, None, None], s, NEG_INF)
    elif window is not None:
        m = jnp.abs(qp[:, None] - kp[None, :]) < window
        s = jnp.where(m[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskh->btkgh", p, v)
    return out.reshape(B, T, H, hd)


# ---------------------------------------------------------------------------
# Standard GQA attention layer
# ---------------------------------------------------------------------------

def attn_desc(cfg: ModelConfig) -> Dict[str, ParamDesc]:
    d, hd = cfg.d_model, cfg.hd
    desc = {
        "wq": ParamDesc((d, cfg.num_heads * hd), ("embed", "heads")),
        "wk": ParamDesc((d, cfg.num_kv_heads * hd), ("embed", "kv")),
        "wv": ParamDesc((d, cfg.num_kv_heads * hd), ("embed", "kv")),
        "wo": ParamDesc((cfg.num_heads * hd, d), ("heads", "embed")),
    }
    if cfg.qk_norm:
        desc["q_norm"] = norm_desc(hd)
        desc["k_norm"] = norm_desc(hd)
    return desc


def _project_qkv(params, cfg: ModelConfig, x, positions):
    B, T, _ = x.shape
    hd = cfg.hd
    q = (x @ params["wq"]).reshape(B, T, cfg.num_heads, hd)
    k = (x @ params["wk"]).reshape(B, T, cfg.num_kv_heads, hd)
    v = (x @ params["wv"]).reshape(B, T, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, eps=cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, eps=cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_forward(params, cfg: ModelConfig, spec: LayerSpec, x, positions):
    """Full-sequence causal attention (train / prefill). x: (B, T, d)."""
    B, T, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions)
    out = flash_attention(q, k, v, causal=True, window=spec.window,
                          softcap=cfg.attn_logit_softcap)
    return out.reshape(B, T, -1) @ params["wo"]


def attn_prefill(params, cfg: ModelConfig, spec: LayerSpec, x, positions,
                 max_len: int):
    """Full-sequence attention that also emits the decode cache.

    Full-attention layers cache all T entries (padded to ``max_len``);
    sliding-window layers keep a ring buffer of the last ``window`` entries,
    rolled so that entry for position p sits at slot p % window.
    """
    B, T, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions)
    out = flash_attention(q, k, v, causal=True, window=spec.window,
                          softcap=cfg.attn_logit_softcap)
    out = out.reshape(B, T, -1) @ params["wo"]

    def to_cache(arr):
        if spec.window and spec.window < max_len:
            W = min(spec.window, T)
            tail = arr[:, T - W:]
            if T > W:
                tail = jnp.roll(tail, shift=(T - W) % W, axis=1)
            L = min(spec.window, max_len)
            return jnp.pad(tail, ((0, 0), (0, L - W), (0, 0), (0, 0)))
        return jnp.pad(arr, ((0, 0), (0, max_len - T), (0, 0), (0, 0)))

    return out, {"k": to_cache(k), "v": to_cache(v)}


def init_attn_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, max_len: int,
                    dtype) -> Dict[str, jax.ShapeDtypeStruct]:
    """Cache shapes for one attention layer.  Sliding-window layers keep a
    ring buffer of ``window`` entries instead of the full context."""
    L = min(max_len, spec.window) if spec.window else max_len
    shape = (batch, L, cfg.num_kv_heads, cfg.hd)
    return {"k": jax.ShapeDtypeStruct(shape, dtype),
            "v": jax.ShapeDtypeStruct(shape, dtype)}


def attn_decode(params, cfg: ModelConfig, spec: LayerSpec, x, cache, pos):
    """One-token decode.  x: (B, 1, d); cache: {'k','v'} (B, L, KV, hd);
    pos: scalar int32 — number of tokens already in the cache — or an (B,)
    int32 vector of PER-ROW positions (the serving engine's continuous
    batch, where every slot sits at its own depth; DESIGN.md §12).  The
    scalar path is unchanged; the vector path stores per row via a one-hot
    ``where`` write (bit-identical values to the per-row dynamic slice)."""
    B = x.shape[0]
    hd = cfg.hd
    pos = jnp.asarray(pos, jnp.int32)
    vec = pos.ndim == 1
    positions = pos[:, None] if vec else jnp.full((B, 1), pos, dtype=jnp.int32)
    q, k, v = _project_qkv(params, cfg, x, positions)
    L = cache["k"].shape[1]
    slot = pos % L if spec.window else pos
    if vec:
        k_cache = _store_rows(cache["k"], k, slot)
        v_cache = _store_rows(cache["v"], v, slot)
    else:
        k_cache = _dynamic_store(cache["k"], k, slot)
        v_cache = _dynamic_store(cache["v"], v, slot)

    # positions actually stored in each cache slot (ring-aware).  ``p_row``
    # broadcasts the per-row/scalar cases through one set of formulas:
    # valid is (B, L) on the vector path, (L,) on the scalar path.
    idx = jnp.arange(L)
    p_row = pos[:, None] if vec else pos
    if spec.window:
        # slot i holds position p with p % L == i and p <= pos; invalid if p > pos
        # or evicted (pos - p >= window).
        base = p_row - (p_row % L)
        cand = jnp.where(idx <= (p_row % L), base + idx, base - L + idx)
        valid = (cand >= 0) & (cand <= p_row) & ((p_row - cand) < spec.window)
    else:
        valid = idx <= p_row
    vmask = (valid[:, None, None, None, :] if vec
             else valid[None, None, None, None, :])

    qg = q.reshape(B, 1, cfg.num_kv_heads, -1, hd)
    s = jnp.einsum("btkgh,blkh->bkgtl", qg, k_cache,
                   preferred_element_type=jnp.float32) / np.sqrt(hd)
    if cfg.attn_logit_softcap is not None:
        s = cfg.attn_logit_softcap * jnp.tanh(s / cfg.attn_logit_softcap)
    s = jnp.where(vmask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgtl,blkh->btkgh", p, v_cache).reshape(B, 1, -1)
    return out @ params["wo"], {"k": k_cache, "v": v_cache}


def _dynamic_store(cache, new, slot):
    return jax.lax.dynamic_update_slice_in_dim(
        cache, new.astype(cache.dtype), slot, axis=1)


def _store_rows(cache, new, slot):
    """Per-row store: new[b, 0] lands at cache[b, slot[b]] — the
    vector-``pos`` twin of :func:`_dynamic_store`.  cache: (B, L, ...);
    new: (B, 1, ...); slot: (B,) int32."""
    L = cache.shape[1]
    hit = jnp.arange(L)[None, :] == slot[:, None]             # (B, L)
    hit = hit.reshape(hit.shape + (1,) * (cache.ndim - 2))
    return jnp.where(hit, new.astype(cache.dtype), cache)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_desc(cfg: ModelConfig) -> Dict[str, ParamDesc]:
    d, H = cfg.d_model, cfg.num_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "wq": ParamDesc((d, H * qk), ("embed", "heads")),
        "w_dkv": ParamDesc((d, cfg.kv_lora_rank + cfg.qk_rope_dim), ("embed", "lora")),
        "kv_norm": norm_desc(cfg.kv_lora_rank),
        "w_ukv": ParamDesc((cfg.kv_lora_rank,
                            H * (cfg.qk_nope_dim + cfg.v_head_dim)), ("lora", "heads")),
        "wo": ParamDesc((H * cfg.v_head_dim, d), ("heads", "embed")),
    }


def _mla_qkv(params, cfg: ModelConfig, x, positions):
    B, T, _ = x.shape
    H = cfg.num_heads
    nope, rope, vdim = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q = (x @ params["wq"]).reshape(B, T, H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    # kimi-linear's MLA (mla_rope False) keeps the split, without rotation
    if cfg.mla_rope:
        q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    latent = x @ params["w_dkv"]
    c_kv = rmsnorm(params["kv_norm"], latent[..., :cfg.kv_lora_rank], eps=cfg.norm_eps)
    k_rope = latent[..., None, cfg.kv_lora_rank:]
    if cfg.mla_rope:
        k_rope = apply_rope(k_rope, positions, cfg.rope_theta)
    return q_nope, q_rope, c_kv, k_rope


def _mla_expand_kv(params, cfg: ModelConfig, c_kv):
    """Up-project latents to per-head K_nope and V."""
    B, L, _ = c_kv.shape
    H, nope, vdim = cfg.num_heads, cfg.qk_nope_dim, cfg.v_head_dim
    kv = (c_kv @ params["w_ukv"]).reshape(B, L, H, nope + vdim)
    return kv[..., :nope], kv[..., nope:]


def mla_forward(params, cfg: ModelConfig, spec: LayerSpec, x, positions):
    B, T, _ = x.shape
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(params, cfg, x, positions)
    k_nope, v = _mla_expand_kv(params, cfg, c_kv)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, (B, T, cfg.num_heads, cfg.qk_rope_dim))], axis=-1)
    # pad V to q/k head_dim so the shared flash kernel applies, then crop
    pad = q.shape[-1] - cfg.v_head_dim
    v_p = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))
    out = flash_attention(q, k, v_p, causal=True)[..., :cfg.v_head_dim]
    return out.reshape(B, T, -1) @ params["wo"]


def mla_prefill(params, cfg: ModelConfig, spec: LayerSpec, x, positions,
                max_len: int):
    B, T, _ = x.shape
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(params, cfg, x, positions)
    k_nope, v = _mla_expand_kv(params, cfg, c_kv)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, (B, T, cfg.num_heads, cfg.qk_rope_dim))], axis=-1)
    pad = q.shape[-1] - cfg.v_head_dim
    v_p = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))
    out = flash_attention(q, k, v_p, causal=True)[..., :cfg.v_head_dim]
    out = out.reshape(B, T, -1) @ params["wo"]
    cache = {
        "c_kv": jnp.pad(c_kv, ((0, 0), (0, max_len - T), (0, 0))),
        "k_rope": jnp.pad(k_rope, ((0, 0), (0, max_len - T), (0, 0), (0, 0))),
    }
    return out, cache


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    return {"c_kv": jax.ShapeDtypeStruct((batch, max_len, cfg.kv_lora_rank), dtype),
            "k_rope": jax.ShapeDtypeStruct((batch, max_len, 1, cfg.qk_rope_dim), dtype)}


def mla_decode(params, cfg: ModelConfig, spec: LayerSpec, x, cache, pos,
               absorb: bool = False):
    """One-token MLA decode against the latent cache.

    ``absorb=False`` (paper-naive): up-project every cached latent each step.
    ``absorb=True`` (optimized): fold W_uk into the query and W_uv into the
    output projection so attention runs directly in the latent space —
    removes the (L, H, nope+v) materialization (see EXPERIMENTS.md §Perf).
    """
    B = x.shape[0]
    H, nope, rope, vdim = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    pos = jnp.asarray(pos, jnp.int32)
    vec = pos.ndim == 1           # per-row positions (serving; DESIGN.md §12)
    positions = pos[:, None] if vec else jnp.full((B, 1), pos, dtype=jnp.int32)
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkv(params, cfg, x, positions)
    if vec:
        c_cache = _store_rows(cache["c_kv"], c_kv_new, pos)
        r_cache = _store_rows(cache["k_rope"], k_rope_new, pos)
    else:
        c_cache = _dynamic_store(cache["c_kv"], c_kv_new, pos)
        r_cache = _dynamic_store(cache["k_rope"], k_rope_new, pos)
    L = c_cache.shape[1]
    if vec:
        valid = (jnp.arange(L)[None, :] <= pos[:, None])[:, None, None, :]
    else:
        valid = (jnp.arange(L) <= pos)[None, None, None, :]

    w_ukv = params["w_ukv"].reshape(cfg.kv_lora_rank, H, nope + vdim)
    w_uk, w_uv = w_ukv[..., :nope], w_ukv[..., nope:]

    if absorb:
        # q_lat: (B, 1, H, lora) = q_nope @ W_uk^T  (per head)
        q_lat = jnp.einsum("bthn,lhn->bthl", q_nope, w_uk)
        s = jnp.einsum("bthl,bLl->bhtL", q_lat, c_cache,
                       preferred_element_type=jnp.float32)
        s += jnp.einsum("bthr,bLkr->bhtL", q_rope, r_cache,
                        preferred_element_type=jnp.float32)
        s = s / np.sqrt(nope + rope)
        p = jax.nn.softmax(jnp.where(valid, s, NEG_INF), axis=-1)
        o_lat = jnp.einsum("bhtL,bLl->bthl", p.astype(c_cache.dtype), c_cache)
        out = jnp.einsum("bthl,lhv->bthv", o_lat, w_uv)
    else:
        k_nope, v = _mla_expand_kv(params, cfg, c_cache)   # (B, L, H, ·)
        s = jnp.einsum("bthn,bLhn->bhtL", q_nope, k_nope,
                       preferred_element_type=jnp.float32)
        s += jnp.einsum("bthr,bLkr->bhtL", q_rope, r_cache,
                        preferred_element_type=jnp.float32)
        s = s / np.sqrt(nope + rope)
        p = jax.nn.softmax(jnp.where(valid, s, NEG_INF), axis=-1)
        out = jnp.einsum("bhtL,bLhv->bthv", p.astype(v.dtype), v)
    out = out.reshape(B, 1, H * vdim) @ params["wo"]
    return out, {"c_kv": c_cache, "k_rope": r_cache}
