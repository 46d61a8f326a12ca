"""Encoder-decoder stack for SeamlessM4T-large-v2.

The speech frontend (mel-spectrogram + conformer feature extractor) is the
allowed modality STUB: the encoder consumes precomputed frame embeddings
(B, S, d_model).  The encoder is a bidirectional transformer; the decoder is
a causal transformer with cross-attention over the encoder memory.  Decode
caches both the self-attention KV and the (constant) projected cross KV.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import attention as attn
from repro.models.layers import ParamDesc, mlp, mlp_desc, norm_desc, rmsnorm
from repro.models.transformer import stack_desc

CROSS_SPEC = LayerSpec(mixer="attn", window=None, ffn="dense")


def cross_attn_desc(cfg: ModelConfig) -> Dict[str, ParamDesc]:
    d, hd = cfg.d_model, cfg.hd
    return {
        "wq": ParamDesc((d, cfg.num_heads * hd), ("embed", "heads")),
        "wk": ParamDesc((d, cfg.num_kv_heads * hd), ("embed", "kv")),
        "wv": ParamDesc((d, cfg.num_kv_heads * hd), ("embed", "kv")),
        "wo": ParamDesc((cfg.num_heads * hd, d), ("heads", "embed")),
    }


def cross_kv(params, cfg: ModelConfig, memory):
    B, S, _ = memory.shape
    k = (memory @ params["wk"]).reshape(B, S, cfg.num_kv_heads, cfg.hd)
    v = (memory @ params["wv"]).reshape(B, S, cfg.num_kv_heads, cfg.hd)
    return k, v


def cross_attend(params, cfg: ModelConfig, x, k, v):
    """x: (B, T, d); k, v: (B, S, KV, hd). No mask, no RoPE (enc-dec)."""
    B, T, _ = x.shape
    q = (x @ params["wq"]).reshape(B, T, cfg.num_heads, cfg.hd)
    out = attn.flash_attention(q, k, v, causal=False)
    return out.reshape(B, T, -1) @ params["wo"]


def dec_block_desc(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "norm1": norm_desc(cfg.d_model),
        "self": attn.attn_desc(cfg),
        "norm_x": norm_desc(cfg.d_model),
        "cross": cross_attn_desc(cfg),
        "norm2": norm_desc(cfg.d_model),
        "ffn": mlp_desc(cfg.d_model, cfg.d_ff),
    }


def dec_block_train(params, cfg: ModelConfig, x, positions, memory):
    with jax.named_scope("attn"), jax.named_scope("attn"):
        h = rmsnorm(params["norm1"], x, eps=cfg.norm_eps)
        h = attn.attn_forward(params["self"], cfg, CROSS_SPEC, h, positions)
    x = x + h
    with jax.named_scope("attn"), jax.named_scope("cross"):
        h = rmsnorm(params["norm_x"], x, eps=cfg.norm_eps)
        k, v = cross_kv(params["cross"], cfg, memory)
        h = cross_attend(params["cross"], cfg, h, k, v)
    x = x + h
    with jax.named_scope("mlp"):
        h = rmsnorm(params["norm2"], x, eps=cfg.norm_eps)
        h = mlp(params["ffn"], h, cfg.activation)
    return x + h


def dec_block_prefill(params, cfg: ModelConfig, x, positions, memory, max_len):
    h = rmsnorm(params["norm1"], x, eps=cfg.norm_eps)
    sa, self_cache = attn.attn_prefill(params["self"], cfg, CROSS_SPEC, h,
                                       positions, max_len)
    x = x + sa
    h = rmsnorm(params["norm_x"], x, eps=cfg.norm_eps)
    k, v = cross_kv(params["cross"], cfg, memory)
    x = x + cross_attend(params["cross"], cfg, h, k, v)
    h = rmsnorm(params["norm2"], x, eps=cfg.norm_eps)
    x = x + mlp(params["ffn"], h, cfg.activation)
    return x, {"self": self_cache, "cross_k": k, "cross_v": v}


def dec_block_cache(cfg: ModelConfig, batch: int, max_len: int, src_len: int, dtype):
    self_cache = attn.init_attn_cache(cfg, CROSS_SPEC, batch, max_len, dtype)
    kv = jax.ShapeDtypeStruct((batch, src_len, cfg.num_kv_heads, cfg.hd), dtype)
    return {"self": self_cache, "cross_k": kv, "cross_v": kv}


def dec_block_decode(params, cfg: ModelConfig, x, cache, pos):
    h = rmsnorm(params["norm1"], x, eps=cfg.norm_eps)
    sa, self_cache = attn.attn_decode(params["self"], cfg, CROSS_SPEC, h,
                                      cache["self"], pos)
    x = x + sa
    h = rmsnorm(params["norm_x"], x, eps=cfg.norm_eps)
    x = x + cross_attend(params["cross"], cfg, h, cache["cross_k"], cache["cross_v"])
    h = rmsnorm(params["norm2"], x, eps=cfg.norm_eps)
    x = x + mlp(params["ffn"], h, cfg.activation)
    return x, {"self": self_cache, "cross_k": cache["cross_k"],
               "cross_v": cache["cross_v"]}


# ---------------------------------------------------------------------------
# Stacks (uniform layers -> one scan each)
# ---------------------------------------------------------------------------

def encdec_desc(cfg: ModelConfig) -> Dict[str, Any]:
    from repro.models.transformer import block_desc
    enc_spec = LayerSpec(mixer="attn", window=None, ffn="dense")
    enc_block = block_desc(cfg, enc_spec)
    dec_block = dec_block_desc(cfg)
    return {
        "enc_stack": stack_desc(enc_block, cfg.num_encoder_layers),
        "enc_norm": norm_desc(cfg.d_model),
        "dec_stack": stack_desc(dec_block, cfg.num_layers),
        "dec_norm": norm_desc(cfg.d_model),
    }


def encode(params, cfg: ModelConfig, src):
    """src: (B, S, d) precomputed frame embeddings (frontend stub)."""
    from repro.models.transformer import block_train
    enc_spec = LayerSpec(mixer="attn", window=None, ffn="dense")
    B, S, _ = src.shape
    positions = jnp.arange(S)[None, :]

    @jax.checkpoint
    def body_fn(h, p):
        h, _ = block_train(p, cfg, enc_spec, h, positions, causal=False)
        return h

    x, _ = jax.lax.scan(lambda h, p: (body_fn(h, p), None), src,
                        params["enc_stack"])
    return rmsnorm(params["enc_norm"], x, eps=cfg.norm_eps)


def decode_train(params, cfg: ModelConfig, x, positions, memory):
    @jax.checkpoint
    def body_fn(h, p):
        return dec_block_train(p, cfg, h, positions, memory)

    x, _ = jax.lax.scan(lambda h, p: (body_fn(h, p), None), x,
                        params["dec_stack"])
    return rmsnorm(params["dec_norm"], x, eps=cfg.norm_eps)


def decode_prefill(params, cfg: ModelConfig, x, positions, memory, max_len):
    def body(h, p):
        h, cache = dec_block_prefill(p, cfg, h, positions, memory, max_len)
        return h, cache

    x, caches = jax.lax.scan(body, x, params["dec_stack"])
    return rmsnorm(params["dec_norm"], x, eps=cfg.norm_eps), caches


def decode_step_stack(params, cfg: ModelConfig, x, caches, pos):
    def body(h, inp):
        p, c = inp
        h, nc = dec_block_decode(p, cfg, h, c, pos)
        return h, nc

    x, new_caches = jax.lax.scan(body, x, (params["dec_stack"], caches))
    return rmsnorm(params["dec_norm"], x, eps=cfg.norm_eps), new_caches
