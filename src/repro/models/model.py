"""Model facade: one object per architecture config exposing

  * ``param_desc`` / ``init`` / ``partition_specs``
  * ``loss(params, batch)``                      (train)
  * ``prefill(params, batch)``                   (inference prefill)
  * ``decode_step(params, tokens, cache, pos)``  (inference decode)
  * ``input_specs(shape)`` / ``input_partition_specs(shape)``  (dry-run)

covering decoder-only (dense/MoE/SSM/hybrid/VLM) and encoder-decoder (audio)
families.  Cross-entropy is computed in sequence chunks so the full
(B, T, vocab) logits tensor is never materialized.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec, moe, transformer
from repro.models.layers import (ParamDesc, abstract_params, embed,
                                 embedding_desc, materialize, norm_desc,
                                 partition_specs, rmsnorm, sharding_rules,
                                 softmax_xent)

XENT_CHUNK = 512


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.plan = cfg.stack_plan()

    # -- parameters ---------------------------------------------------------

    def param_desc(self) -> Dict[str, Any]:
        cfg = self.cfg
        desc: Dict[str, Any] = {
            "embed": embedding_desc(cfg.padded_vocab, cfg.d_model),
            "final_norm": norm_desc(cfg.d_model),
        }
        if cfg.is_encoder_decoder:
            desc["encdec"] = encdec.encdec_desc(cfg)
        else:
            desc["stack"] = transformer.stack_desc_tree(cfg, self.plan)
        if not cfg.tie_embeddings:
            desc["lm_head"] = embedding_desc(cfg.padded_vocab, cfg.d_model)
        return desc

    def init(self, rng, dtype=None):
        dtype = dtype or _dtype(self.cfg.param_dtype)
        return materialize(self.param_desc(), rng, dtype)

    def abstract_params(self, dtype=None):
        dtype = dtype or _dtype(self.cfg.param_dtype)
        return abstract_params(self.param_desc(), dtype)

    def partition_specs(self, phase: str, multi_pod: bool = False):
        rules = sharding_rules(phase, multi_pod)
        return partition_specs(self.param_desc(), rules)

    # -- shared pieces ------------------------------------------------------

    def _embed(self, params, tokens):
        from repro.models.sharding_ctx import constrain
        with jax.named_scope("embed"):
            x = embed(params["embed"], tokens, scale=self.cfg.embed_scale,
                      d=self.cfg.d_model).astype(
                          _dtype(self.cfg.compute_dtype))
            return constrain(x, ("b", None, None))

    def _lm_table(self, params):
        return params["embed" if self.cfg.tie_embeddings else "lm_head"]["table"]

    def _backbone_train(self, params, batch):
        cfg = self.cfg
        if cfg.is_encoder_decoder:
            memory = encdec.encode(params["encdec"], cfg, batch["src"])
            tokens = batch["tokens"]
            x = self._embed(params, tokens)
            positions = jnp.arange(tokens.shape[1])[None, :]
            h = encdec.decode_train(params["encdec"], cfg, x, positions, memory)
            return h, moe.no_aux()
        tokens = batch["tokens"]
        x = self._embed(params, tokens)
        positions = jnp.arange(tokens.shape[1])[None, :]
        return transformer.stack_train(params["stack"], cfg, self.plan, x,
                                       positions)

    def _chunked_xent(self, params, h, labels, mask=None):
        """h: (B, T, d); labels: (B, T). Scan over T chunks; logits are never
        materialized at full length."""
        cfg = self.cfg
        B, T, d = h.shape
        c = min(XENT_CHUNK, T)
        n = T // c
        table = self._lm_table(params)

        @jax.checkpoint
        def chunk_loss(hc, lc):
            # rematerialized in backward: the (B, c, vocab) logits never
            # survive the forward pass
            logits = hc @ table.T
            if cfg.final_logit_softcap:
                logits = cfg.final_logit_softcap * jnp.tanh(
                    logits / cfg.final_logit_softcap)
            mc = lc >= 0
            nll = softmax_xent(logits, jnp.maximum(lc, 0), mc)
            return nll, jnp.sum(mc.astype(jnp.float32))

        def body(carry, i):
            tot, cnt = carry
            hc = jax.lax.dynamic_slice_in_dim(h, i * c, c, axis=1)
            lc = jax.lax.dynamic_slice_in_dim(labels, i * c, c, axis=1)
            nll, k = chunk_loss(hc, lc)
            return (tot + nll * k, cnt + k), None

        (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                     jnp.arange(n))
        rem = T - n * c
        if rem:
            logits = h[:, n * c:] @ table.T
            lc = labels[:, n * c:]
            mc = lc >= 0
            nll = softmax_xent(logits, jnp.maximum(lc, 0), mc)
            k = jnp.sum(mc.astype(jnp.float32))
            tot, cnt = tot + nll * k, cnt + k
        return tot / jnp.maximum(cnt, 1.0)

    # -- training -----------------------------------------------------------

    def loss(self, params, batch):
        """Next-token LM loss (+ MoE aux). Labels are tokens shifted left;
        the final position is masked with -1."""
        return self.loss_and_counts(params, batch)[0]

    def loss_and_counts(self, params, batch):
        """``(loss, counts)``: :meth:`loss` and the MoE layers' capacity
        counters summed over the stack, ``{"moe_dropped", "moe_routed"}``
        (token choices dropped to overflow / routed; 0 without experts).
        The embedding runs under the ``embed`` scope, the final norm and
        the cross-entropy under ``head``."""
        tokens = batch["tokens"]
        labels = jnp.concatenate(
            [tokens[:, 1:], -jnp.ones_like(tokens[:, :1])], axis=1)
        h, aux = self._backbone_train(params, batch)
        with jax.named_scope("head"):
            if not self.cfg.is_encoder_decoder:
                h = rmsnorm(params["final_norm"], h, eps=self.cfg.norm_eps)
            nll = self._chunked_xent(params, h, labels)
        loss = nll + self.cfg.router_aux_coef * aux["balance"]
        return loss, {"moe_dropped": aux["dropped"],
                      "moe_routed": aux["routed"]}

    # -- inference ----------------------------------------------------------

    def prefill(self, params, batch, max_len: Optional[int] = None):
        """Returns (last-token logits, cache)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, T = tokens.shape
        max_len = max_len or T
        x = self._embed(params, tokens)
        positions = jnp.arange(T)[None, :]
        if cfg.is_encoder_decoder:
            memory = encdec.encode(params["encdec"], cfg, batch["src"])
            h, cache = encdec.decode_prefill(params["encdec"], cfg, x, positions,
                                             memory, max_len)
        else:
            h, _, cache = transformer.stack_prefill(params["stack"], cfg, self.plan,
                                                    x, positions, max_len)
            h = rmsnorm(params["final_norm"], h, eps=cfg.norm_eps)
        logits = h[:, -1:] @ self._lm_table(params).T
        if cfg.final_logit_softcap:
            logits = cfg.final_logit_softcap * jnp.tanh(
                logits / cfg.final_logit_softcap)
        return logits, cache

    def init_cache(self, batch: int, max_len: int, src_len: int = 0, dtype=None):
        cfg = self.cfg
        dtype = dtype or _dtype(cfg.compute_dtype)
        if cfg.is_encoder_decoder:
            one = encdec.dec_block_cache(cfg, batch, max_len, src_len, dtype)
            return jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((cfg.num_layers,) + s.shape, s.dtype),
                one)
        return transformer.stack_cache(cfg, self.plan, batch, max_len, dtype)

    def decode_step(self, params, tokens, cache, pos, mla_absorb: bool = False,
                    moe_dispatch: bool = False):
        """tokens: (B, 1) int32; pos: scalar int32 (tokens already cached)
        or an (B,) int32 vector of per-row depths (continuous batching —
        every serving slot decodes at its own position; DESIGN.md §12).
        Returns (logits (B, 1, vocab), new_cache)."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        if cfg.is_encoder_decoder:
            h, new_cache = encdec.decode_step_stack(params["encdec"], cfg, x,
                                                    cache, pos)
        else:
            h, new_cache = transformer.stack_decode(params["stack"], cfg, self.plan,
                                                    x, cache, pos, mla_absorb,
                                                    moe_dispatch)
            h = rmsnorm(params["final_norm"], h, eps=cfg.norm_eps)
        logits = h @ self._lm_table(params).T
        if cfg.final_logit_softcap:
            logits = cfg.final_logit_softcap * jnp.tanh(
                logits / cfg.final_logit_softcap)
        return logits, new_cache

    # -- dry-run specs ------------------------------------------------------

    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every step-function input."""
        cfg = self.cfg
        B, T = shape.global_batch, shape.seq_len
        cdt = _dtype(cfg.compute_dtype)
        if shape.phase in ("train", "prefill"):
            specs = {"tokens": jax.ShapeDtypeStruct((B, T), jnp.int32)}
            if cfg.is_encoder_decoder:
                specs["src"] = jax.ShapeDtypeStruct((B, T, cfg.d_model), cdt)
            return specs
        # decode: one new token against a T-entry cache
        src_len = T if cfg.is_encoder_decoder else 0
        return {
            "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
            "cache": self.init_cache(B, T, src_len=src_len, dtype=cdt),
            "pos": jax.ShapeDtypeStruct((), jnp.int32),
        }

    def input_partition_specs(self, shape: ShapeConfig, multi_pod: bool = False):
        """PartitionSpecs matching input_specs()."""
        cfg = self.cfg
        data = ("pod", "data") if multi_pod else "data"
        B = shape.global_batch
        batch_axis = data if B > 1 else None
        if shape.phase in ("train", "prefill"):
            specs = {"tokens": P(batch_axis, None)}
            if cfg.is_encoder_decoder:
                specs["src"] = P(batch_axis, None, None)
            return specs
        # decode cache sharding (name-based; see DESIGN.md §3):
        #   * batch over the data axes (when B > 1)
        #   * attention K/V: KV-head dim over 'model' when divisible, else the
        #     cache LENGTH over 'model' (sequence-parallel decode — partial
        #     attention per shard, softmax/psum combine handled by SPMD)
        #   * MLA latents: length over 'model'
        #   * recurrent states: d_inner over 'model'
        #   * B == 1 long-context: length over 'data' too (flash-decoding
        #     style maximum parallelism)
        model_n = 16  # production model-axis size (no-op on smaller meshes)

        def cache_spec(path, s: jax.ShapeDtypeStruct) -> P:
            name = next((str(p.key) for p in reversed(path)
                         if hasattr(p, "key")), "")
            nd = len(s.shape)
            spec = [None] * nd
            bi = next((i for i in range(min(nd, 2)) if s.shape[i] == B), None)
            if bi is None:
                return P(*spec)
            if B > 1:
                spec[bi] = batch_axis
            li = bi + 1  # length dim, when the leaf has one
            if name in ("k", "v", "cross_k", "cross_v"):
                kv_dim = bi + 2
                if s.shape[kv_dim] % model_n == 0:
                    spec[kv_dim] = "model"
                elif li < nd and s.shape[li] % model_n == 0 and s.shape[li] >= 2048:
                    spec[li] = "model"
            elif name in ("c_kv", "k_rope"):
                if li < nd and s.shape[li] % model_n == 0 and s.shape[li] >= 2048:
                    spec[li] = "model"
            elif name in ("h", "conv", "C"):
                fi = max(range(bi + 1, nd), key=lambda i: s.shape[i])
                if s.shape[fi] % model_n == 0:
                    spec[fi] = "model"
            if B == 1 and li < nd and s.shape[li] >= 4096:
                axes = list(data) if isinstance(data, tuple) else [data]
                if spec[li] is None:
                    spec[li] = tuple(axes)
                elif spec[li] == "model":
                    spec[li] = tuple(axes) + ("model",)
            return P(*spec)

        cache = jax.tree_util.tree_map_with_path(cache_spec, self.init_cache(
            B, shape.seq_len, src_len=shape.seq_len if cfg.is_encoder_decoder else 0,
            dtype=_dtype(cfg.compute_dtype)))
        return {"tokens": P(batch_axis, None), "cache": cache, "pos": P()}


# ---------------------------------------------------------------------------

def count_params(cfg: ModelConfig) -> int:
    model = Model(cfg)
    leaves = jax.tree.leaves(model.param_desc(),
                             is_leaf=lambda x: isinstance(x, ParamDesc))
    return int(sum(np.prod(l.shape) for l in leaves))
