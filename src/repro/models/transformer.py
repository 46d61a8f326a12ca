"""Decoder stack: config-driven blocks (attention / MLA / Mamba / xLSTM ×
dense / MoE FFN), lowered as ``lax.scan`` over repeating layer periods so HLO
size stays O(period) instead of O(num_layers).

Three entry points per stack:
  * ``forward_train``  — full-sequence, returns (hidden, aux_loss)
  * ``forward_prefill``— full-sequence, additionally returns the decode cache
  * ``decode_step``    — one token against the cache (B, 1, d)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig, Segment
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import (ParamDesc, mlp, mlp_desc, mlp_tp, norm_desc,
                                 rmsnorm, stack_desc)
from repro.models.sharding_ctx import constrain, tp_axis


# ---------------------------------------------------------------------------
# One block
# ---------------------------------------------------------------------------

def block_desc(cfg: ModelConfig, spec: LayerSpec) -> Dict[str, Any]:
    desc: Dict[str, Any] = {}
    if spec.mixer in ("mlstm", "slstm"):
        # xLSTM blocks carry their own norms and FFN
        desc["mixer"] = (xlstm_mod.mlstm_desc(cfg) if spec.mixer == "mlstm"
                         else xlstm_mod.slstm_desc(cfg))
        return desc
    desc["norm1"] = norm_desc(cfg.d_model)
    if spec.mixer == "attn":
        desc["mixer"] = attn.attn_desc(cfg)
    elif spec.mixer == "mla":
        desc["mixer"] = attn.mla_desc(cfg)
    elif spec.mixer == "mamba":
        desc["mixer"] = ssm_mod.mamba_desc(cfg)
    else:
        raise ValueError(spec.mixer)
    if spec.ffn != "none":
        desc["norm2"] = norm_desc(cfg.d_model)
        desc["ffn"] = (moe_mod.moe_desc(cfg) if spec.ffn == "moe"
                       else mlp_desc(cfg.d_model, cfg.d_ff))
    return desc


def _boundary(h):
    """Block-boundary barrier: stops XLA hoisting the next norm's f32
    upcast through the tensor-parallel partial-sum all-reduce — keeps those
    activation reductions in bf16 (2x wire; see EXPERIMENTS.md §Perf)."""
    return jax.lax.optimization_barrier(h)


def block_train(params, cfg: ModelConfig, spec: LayerSpec, x, positions,
                causal: bool = True):
    """Full-sequence block. Returns (x, aux), ``aux`` as
    :func:`moe.moe_ffn` gives it (:func:`moe.no_aux` without experts).

    The parts run under named scopes, which name their ops in the compiled
    program and the device trace: ``attn/<mixer>`` for the mixer (its
    norm included), ``moe`` or ``mlp`` for the FFN."""
    aux = moe_mod.no_aux()
    if spec.mixer in ("mlstm", "slstm"):
        f = xlstm_mod.mlstm_forward if spec.mixer == "mlstm" else xlstm_mod.slstm_forward
        with jax.named_scope("attn"), jax.named_scope(spec.mixer):
            h = f(params["mixer"], cfg, x)
        return x + _boundary(h), aux
    with jax.named_scope("attn"), jax.named_scope(spec.mixer):
        h = rmsnorm(params["norm1"], x, eps=cfg.norm_eps)
        if spec.mixer == "attn":
            if causal:
                h = attn.attn_forward(params["mixer"], cfg, spec, h, positions)
            else:  # encoder self-attention
                h = _attn_bidirectional(params["mixer"], cfg, spec, h,
                                        positions)
        elif spec.mixer == "mla":
            h = attn.mla_forward(params["mixer"], cfg, spec, h, positions)
        else:  # mamba
            h = ssm_mod.mamba_forward(params["mixer"], cfg, h)
    x = x + _boundary(h)
    if spec.ffn != "none":
        with jax.named_scope("moe" if spec.ffn == "moe" else "mlp"):
            h = rmsnorm(params["norm2"], x, eps=cfg.norm_eps)
            if spec.ffn == "moe":
                h, aux = moe_mod.moe_ffn(params["ffn"], cfg, h)
            elif tp_axis():
                # manual tensor parallelism (DESIGN.md §14): params hold
                # this rank's ffn slice; the Megatron f/g wire reduces
                # activations over the tp axis via collectives.api
                h = mlp_tp(params["ffn"], h, cfg.activation, axis=tp_axis())
            else:
                h = mlp(params["ffn"], h, cfg.activation)
        x = x + _boundary(h)
    return x, aux


def _attn_bidirectional(params, cfg, spec, x, positions):
    B, T, _ = x.shape
    q, k, v = attn._project_qkv(params, cfg, x, positions)
    out = attn.flash_attention(q, k, v, causal=False, window=spec.window,
                               softcap=cfg.attn_logit_softcap)
    return out.reshape(B, T, -1) @ params["wo"]


def block_prefill(params, cfg: ModelConfig, spec: LayerSpec, x, positions,
                  max_len: int):
    """Full-sequence block that also emits this layer's decode cache."""
    aux = moe_mod.no_aux()
    if spec.mixer in ("mlstm", "slstm"):
        f = xlstm_mod.mlstm_forward if spec.mixer == "mlstm" else xlstm_mod.slstm_forward
        h, cache = f(params["mixer"], cfg, x, return_state=True)
        return x + h, aux, cache
    h = rmsnorm(params["norm1"], x, eps=cfg.norm_eps)
    if spec.mixer == "attn":
        h, cache = attn.attn_prefill(params["mixer"], cfg, spec, h, positions, max_len)
    elif spec.mixer == "mla":
        h, cache = attn.mla_prefill(params["mixer"], cfg, spec, h, positions, max_len)
    else:
        h, cache = ssm_mod.mamba_forward(params["mixer"], cfg, h, return_state=True)
    x = x + h
    if spec.ffn != "none":
        h = rmsnorm(params["norm2"], x, eps=cfg.norm_eps)
        if spec.ffn == "moe":
            h, aux = moe_mod.moe_ffn(params["ffn"], cfg, h)
        else:
            h = mlp(params["ffn"], h, cfg.activation)
        x = x + h
    return x, aux, cache


def block_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, max_len: int,
                dtype) -> Optional[Dict[str, jax.ShapeDtypeStruct]]:
    if spec.mixer == "attn":
        return attn.init_attn_cache(cfg, spec, batch, max_len, dtype)
    if spec.mixer == "mla":
        return attn.init_mla_cache(cfg, batch, max_len, dtype)
    if spec.mixer == "mamba":
        return ssm_mod.init_mamba_state(cfg, batch, dtype)
    if spec.mixer == "mlstm":
        return xlstm_mod.init_mlstm_state(cfg, batch, dtype)
    if spec.mixer == "slstm":
        return xlstm_mod.init_slstm_state(cfg, batch, dtype)
    raise ValueError(spec.mixer)


def block_decode(params, cfg: ModelConfig, spec: LayerSpec, x, cache, pos,
                 mla_absorb: bool = False, moe_dispatch: bool = False):
    """One-token block step. Returns (x, new_cache).  ``moe_dispatch``
    switches decode MoE from per-token expert-weight GATHER (simple but
    all-gathers expert weights over the model axis every step) to the same
    capacity-dispatch path as training (tokens move, weights stay) — the
    §Perf collective-term optimization for MoE decode."""
    if spec.mixer in ("mlstm", "slstm"):
        f = xlstm_mod.mlstm_decode if spec.mixer == "mlstm" else xlstm_mod.slstm_decode
        h, new_cache = f(params["mixer"], cfg, x, cache)
        return x + h, new_cache
    h = rmsnorm(params["norm1"], x, eps=cfg.norm_eps)
    if spec.mixer == "attn":
        h, new_cache = attn.attn_decode(params["mixer"], cfg, spec, h, cache, pos)
    elif spec.mixer == "mla":
        h, new_cache = attn.mla_decode(params["mixer"], cfg, spec, h, cache, pos,
                                       absorb=mla_absorb)
    else:
        h, new_cache = ssm_mod.mamba_decode(params["mixer"], cfg, h, cache)
    x = x + h
    if spec.ffn != "none":
        h = rmsnorm(params["norm2"], x, eps=cfg.norm_eps)
        if spec.ffn == "moe":
            if moe_dispatch:
                h, _ = moe_mod.moe_ffn(params["ffn"], cfg, h)
            else:
                h = moe_mod.moe_decode_ffn(params["ffn"], cfg, h)
        else:
            h = mlp(params["ffn"], h, cfg.activation)
        x = x + h
    return x, new_cache


# ---------------------------------------------------------------------------
# Stack (scan over segments)
# ---------------------------------------------------------------------------

def stack_desc_tree(cfg: ModelConfig, plan: Tuple[Segment, ...]) -> List[Any]:
    """Descriptor tree: list over segments; each segment is a list over period
    positions of block descriptors, stacked over ``repeats`` when > 1."""
    segs = []
    for seg in plan:
        period = [block_desc(cfg, spec) for spec in seg.period]
        if seg.repeats > 1:
            period = [stack_desc(p, seg.repeats) for p in period]
        segs.append(period)
    return segs


def _sqrt_factor(n: int) -> int:
    """Largest divisor of n that is <= sqrt(n)."""
    best = 1
    f = 1
    while f * f <= n:
        if n % f == 0:
            best = f
        f += 1
    return best


def stack_train(params_segs, cfg: ModelConfig, plan, x, positions,
                causal: bool = True, remat: bool = True):
    """``remat=True`` checkpoints each layer period, and long segments use a
    TWO-LEVEL scan (outer x inner ~ sqrt(repeats)) with the inner scan also
    rematerialized, so the backward pass stores O(outer + inner) layer
    inputs instead of O(repeats) — the sqrt-remat policy that keeps the
    95-layer configs inside 16 GB/chip."""
    aux_total = moe_mod.no_aux()
    for seg, seg_params in zip(plan, params_segs):
        def period_fn(ps, h, seg=seg):
            h = constrain(h, ("b", None, None))
            a = moe_mod.no_aux()
            for spec, p in zip(seg.period, ps):
                def blk(p_, h_, spec=spec):
                    return block_train(p_, cfg, spec, h_, positions, causal)
                if remat and len(seg.period) > 2:
                    # long heterogeneous periods (jamba's 8-layer block,
                    # gemma3's 6): remat per BLOCK too, so the period
                    # backward holds one block's intermediates at a time
                    blk = jax.checkpoint(blk)
                h, aux = blk(p, h)
                a = moe_mod.add_aux(a, aux)
            return h, a

        if remat:
            period_fn = jax.checkpoint(period_fn)

        if seg.repeats == 1:
            x, aux = period_fn(seg_params, x)
            aux_total = moe_mod.add_aux(aux_total, aux)
            continue

        def body(carry, ps, fn=period_fn):
            h, a = carry
            h, aux = fn(ps, h)
            return (h, moe_mod.add_aux(a, aux)), None

        inner = _sqrt_factor(seg.repeats) if remat else 1
        if inner <= 1:
            (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), seg_params)
        else:
            outer = seg.repeats // inner
            ps2 = jax.tree.map(
                lambda p: p.reshape((outer, inner) + p.shape[1:]), seg_params)

            @jax.checkpoint
            def inner_scan(carry, ps_in, body=body):
                out, _ = jax.lax.scan(body, carry, ps_in)
                return out

            def outer_body(carry, ps_in, fn=inner_scan):
                return fn(carry, ps_in), None

            (x, aux_total), _ = jax.lax.scan(outer_body, (x, aux_total), ps2)
    return x, aux_total


def stack_prefill(params_segs, cfg: ModelConfig, plan, x, positions,
                  max_len: int):
    """Returns (x, aux_total, cache) where cache mirrors stack_cache()."""
    aux_total = moe_mod.no_aux()
    caches = []
    for seg, seg_params in zip(plan, params_segs):
        if seg.repeats == 1:
            seg_caches = []
            for spec, p in zip(seg.period, seg_params):
                x, aux, c = block_prefill(p, cfg, spec, x, positions, max_len)
                aux_total = moe_mod.add_aux(aux_total, aux)
                seg_caches.append(c)
            caches.append(seg_caches)
        else:
            def body(carry, ps):
                h, a = carry
                cs = []
                for spec, p in zip(seg.period, ps):
                    h, aux, c = block_prefill(p, cfg, spec, h, positions, max_len)
                    a = moe_mod.add_aux(a, aux)
                    cs.append(c)
                return (h, a), cs

            (x, aux_total), cs = jax.lax.scan(body, (x, aux_total), seg_params)
            caches.append(cs)
    return x, aux_total, caches


def stack_cache(cfg: ModelConfig, plan, batch: int, max_len: int, dtype):
    """ShapeDtypeStruct cache pytree mirroring the segment structure."""
    segs = []
    for seg in plan:
        period = [block_cache(cfg, spec, batch, max_len, dtype) for spec in seg.period]
        if seg.repeats > 1:
            period = [jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((seg.repeats,) + s.shape, s.dtype), p)
                for p in period]
        segs.append(period)
    return segs


# Cache leaves with a per-position length dim — the ones the serving
# engine stores in fixed-size pages (attention K/V, MLA latents).  Every
# other leaf (recurrent h/conv/C, xLSTM states and stabilizers) is carried
# whole per serving slot.  Mirrors the name-based layout knowledge of
# Model.input_partition_specs (DESIGN.md §3/§12).
PAGED_CACHE_LEAVES = ("k", "v", "c_kv", "k_rope")


@dataclasses.dataclass(frozen=True)
class CacheLeafMeta:
    """Per-leaf layout label for the paged serving pool (serve/kv_cache):
    ``kind`` is "paged" (length dim at ``batch_axis + 1``, ``length``
    entries) or "state"; ``batch_axis`` is 1 for leaves stacked over a
    segment's repeats, else 0."""
    kind: str
    batch_axis: int
    length: int


def stack_cache_meta(cfg: ModelConfig, plan, batch: int, max_len: int, dtype):
    """A pytree structurally aligned with :func:`stack_cache` whose leaves
    are :class:`CacheLeafMeta` labels — the serving engine's view of which
    cache leaves page over positions and which are per-slot state."""
    def label(stacked):
        def f(path, s):
            name = path[-1].key if hasattr(path[-1], "key") else ""
            bi = 1 if stacked else 0
            if name in PAGED_CACHE_LEAVES:
                return CacheLeafMeta("paged", bi, int(s.shape[1]))
            return CacheLeafMeta("state", bi, 0)
        return f

    segs = []
    for seg in plan:
        period = [jax.tree_util.tree_map_with_path(
            label(seg.repeats > 1),
            block_cache(cfg, spec, batch, max_len, dtype))
            for spec in seg.period]
        segs.append(period)
    return segs


def materialize_cache(cache_specs):
    """Concrete zero-initialized cache (stabilizer entries 'm' get -1e30)."""
    def init_leaf(path, s):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        if name == "m":
            return jnp.full(s.shape, -1e30, s.dtype)
        return jnp.zeros(s.shape, s.dtype)
    return jax.tree_util.tree_map_with_path(init_leaf, cache_specs)


def stack_decode(params_segs, cfg: ModelConfig, plan, x, cache_segs, pos,
                 mla_absorb: bool = False, moe_dispatch: bool = False):
    """The stacked cache rides the scan CARRY and is updated in place at the
    layer index (``dynamic_update_index_in_dim``), so XLA aliases the cache
    buffer across iterations instead of double-buffering a multi-GiB xs/ys
    pair (critical at decode_32k/long_500k)."""
    new_cache = []
    for seg, seg_params, seg_cache in zip(plan, params_segs, cache_segs):
        if seg.repeats == 1:
            updated = []
            for spec, p, c in zip(seg.period, seg_params, seg_cache):
                x, nc = block_decode(p, cfg, spec, x, c, pos, mla_absorb,
                                     moe_dispatch)
                updated.append(nc)
            new_cache.append(updated)
        else:
            def index_cache(tree, i):
                return jax.tree.map(
                    lambda c: jax.lax.dynamic_index_in_dim(c, i, 0, False), tree)

            def write_cache(tree, new, i):
                return jax.tree.map(
                    lambda c, nc: jax.lax.dynamic_update_index_in_dim(
                        c, nc.astype(c.dtype), i, 0), tree, new)

            def body(carry, inp, seg=seg):
                h, cache_all = carry
                ps, i = inp
                new_list = []
                for spec, p, c in zip(seg.period, ps,
                                      [index_cache(t, i) for t in cache_all]):
                    h, nc = block_decode(p, cfg, spec, h, c, pos, mla_absorb,
                                         moe_dispatch)
                    new_list.append(nc)
                cache_all = [write_cache(t, nc, i)
                             for t, nc in zip(cache_all, new_list)]
                return (h, cache_all), None

            (x, seg_cache), _ = jax.lax.scan(
                body, (x, list(seg_cache)),
                (seg_params, jnp.arange(seg.repeats)))
            new_cache.append(seg_cache)
    return x, new_cache
