"""Mixture-of-Experts FFN with top-k routing, shared experts, capacity-based
dispatch, and a switch-style load-balance auxiliary loss.

Dispatch is the sort-free capacity scheme: each token's k choices are given a
slot inside the chosen expert's capacity buffer via a cumulative-sum over the
one-hot routing matrix; tokens overflowing capacity are dropped (standard
practice, capacity_factor controls the drop rate).  Kept choices and filled
slots are in bijection, so rows move between tokens and the buffer by row
gathers alone, forward and backward (``_dispatch`` and ``_combine``, a
``custom_vjp`` pair whose backward gathers through the inverse map where
autodiff would scatter-add); only the int32 slot map is built by a scatter.
With experts sharded over the ``model`` mesh axis the buffer moves by
all-to-all style collectives — the expert-parallel pattern the survey's §4
discusses.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import ParamDesc, mlp, mlp_desc
from repro.models.sharding_ctx import constrain


def no_aux() -> Dict[str, jnp.ndarray]:
    """What a layer without routed experts adds to the aux path (see
    :func:`moe_ffn`): no balance loss, no token choices routed or dropped."""
    z = jnp.zeros((), jnp.float32)
    return {"balance": z, "dropped": z, "routed": z}


def add_aux(a, b):
    return jax.tree.map(jnp.add, a, b)


def moe_desc(cfg: ModelConfig) -> Dict[str, ParamDesc]:
    d = cfg.d_model
    ff = cfg.moe_d_ff or cfg.d_ff
    E = cfg.num_experts
    desc = {
        "router": ParamDesc((d, E), ("embed", None), "small"),
        "wi_gate": ParamDesc((E, d, ff), ("experts", "embed", "ffn")),
        "wi_up": ParamDesc((E, d, ff), ("experts", "embed", "ffn")),
        "wo": ParamDesc((E, ff, d), ("experts", "ffn", "embed")),
    }
    if cfg.num_shared_experts:
        desc["shared"] = mlp_desc(d, ff * cfg.num_shared_experts)
    return desc


def _route(cfg: ModelConfig, logits: jnp.ndarray):
    """logits: (N, E) -> (weights (N,k), experts (N,k), aux_loss)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(probs, cfg.top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # switch-style load balance: E * sum_e f_e * p_e
    E = logits.shape[-1]
    one_hot = jax.nn.one_hot(experts[..., 0], E, dtype=jnp.float32)
    f = one_hot.mean(0)
    p = probs.mean(0)
    aux = E * jnp.sum(f * p)
    return weights, experts, aux


# Capacity dispatch and combine, per token group.  ``dest[c]`` is the slot
# of flat choice ``c = t*k + j`` (token t's j-th expert), or the sentinel
# ``E*cap`` where the choice was dropped; ``slot_choice[s]`` is the inverse,
# the choice that fills slot s, or the sentinel ``ng*k`` where the slot is
# empty.  A sentinel index is past the end of what it indexes: a gather
# through it reads zeros.

def _invert_slots(dest, n_slots):
    """(ng*k,) slot of each choice -> (n_slots,) choice in each slot."""
    n = dest.shape[0]
    return jnp.full((n_slots,), n, jnp.int32).at[dest].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")


def _rows(a, idx):
    """``a[idx]`` by rows, zeros where ``idx`` is past the end."""
    return jnp.take(a, idx, axis=0, mode="fill", fill_value=0)


def _j_major(a, k):
    """(ng*k, ...) in choice order -> (k, ng, ...): row j holds every
    token's j-th choice, so a sum over j runs over the leading dim and the
    rows keep the (ng, d) tiling (an (ng, k, d) array would pad k)."""
    return jnp.swapaxes(a.reshape(-1, k, *a.shape[1:]), 0, 1)


def _rows_by_choice(a, dest, k):
    """(k, ng, d): the rows of ``a`` at each choice's slot, zeros for a
    dropped choice."""
    idx = _j_major(dest, k).reshape(-1)
    return _rows(a, idx).reshape(k, -1, a.shape[-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, dest, slot_choice, k):
    """(ng, d) tokens -> (E*cap, d) capacity buffer."""
    return _rows(x, slot_choice // k)


def _dispatch_fwd(x, dest, slot_choice, k):
    return _dispatch(x, dest, slot_choice, k), dest


def _dispatch_bwd(k, dest, dbuf):
    # dx[t] = sum_j dbuf[dest[t, j]], a dropped choice adding nothing
    g = _rows_by_choice(dbuf, dest, k).astype(jnp.float32)
    return g.sum(0).astype(dbuf.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _combine(out_flat, w, dest, slot_choice, k):
    """(E*cap, d) expert outputs -> (ng, d): token t gets the sum over its
    kept choices of ``w[c] * out_flat[dest[c]]``."""
    return _combine_fwd(out_flat, w, dest, slot_choice, k)[0]


def _combine_fwd(out_flat, w, dest, slot_choice, k):
    g = _rows_by_choice(out_flat, dest, k)                    # (k, ng, d)
    y = (g.astype(jnp.float32) * _j_major(w, k)[..., None]).sum(0)
    return y.astype(out_flat.dtype), (g, w, slot_choice)


def _combine_bwd(k, res, dy):
    g, w, slot_choice = res
    # d out_flat[s] = w[c] * dy[c // k] for the choice c in slot s
    d_out = (_rows(dy, slot_choice // k).astype(jnp.float32)
             * _rows(w, slot_choice)[:, None]).astype(g.dtype)
    # d w[c] = <dy[c // k], out_flat[dest[c]]>, 0 for a dropped choice
    dw = (g.astype(jnp.float32) * dy.astype(jnp.float32)).sum(-1)
    return d_out, dw.T.reshape(-1).astype(w.dtype), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def moe_ffn(params, cfg: ModelConfig, x, *,
            groups: Optional[int] = None,
            ep_axis: Optional[str] = None,
            a2a_variant: str = "direct"
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B, T, d) -> (out, aux).

    ``aux`` is what the layer sends down the aux path to the loss:
    ``balance``, the load-balance loss, and the capacity counters
    ``routed`` (token choices routed) and ``dropped`` (choices that
    overflowed their expert's buffer and were dropped), float32 device
    scalars over this program's tokens (DESIGN.md §14).

    Tokens are grouped per data shard (per-group capacity — real
    expert-parallel per-rank semantics).  Within a group, choice
    ``c = t*k + j`` (token t's j-th expert) keeps slot ``dest[c]`` of the
    ``(E*cap, d)`` buffer if its expert has room; each filled slot holds
    exactly one kept choice, ``slot_choice[s]``.  So dispatch gathers each
    slot's token row, combine gathers each choice's output row and sums
    the k weighted rows per token (in float32, rounded once), and their
    backward passes gather too: ``dx`` through ``dest``, the buffer's
    cotangent through ``slot_choice``.  The gathers run under ``vmap`` over
    the group dim, which makes G a BATCH dimension the SPMD partitioner can
    shard over the data axes; the expert einsums keep
    explicit (G, E, cap, ·) shapes with G over 'b' and E over 'model' — the
    expert-parallel all-to-all pattern of survey §4.

    ``groups`` overrides the context-derived group count (the conformance
    checks use it to mirror an ep group's source batching on one device).

    ``ep_axis`` names a manual shard_map axis carrying TRUE expert
    parallelism (DESIGN.md §14): ``params`` hold only this rank's
    ``E/ep`` expert block (router replicated, routing still over global
    E), the capacity buffer is exchanged over the wire with
    ``collectives.api.all_to_all`` (dispatch), the local experts run, and
    the reverse all-to-all (combine — also the edge autodiff inserts for
    the backward pass) returns every token's output to its owner.  Chunks
    move verbatim, so the EP step is bit-identical to the same math on
    one device with source-batched groups."""
    from repro.models.sharding_ctx import num_batch_shards
    B, T, d = x.shape
    N = B * T
    E, k = cfg.num_experts, cfg.top_k
    cdt = x.dtype
    G = groups if groups is not None else num_batch_shards()
    if N % G:
        G = 1
    ng = N // G
    cap = int(max(1, ng * k / E * cfg.capacity_factor))
    ep = 1
    if ep_axis is not None:
        ep = jax.lax.axis_size(ep_axis)
        if G != 1:
            raise ValueError(f"ep_axis={ep_axis!r} wants one token group "
                             f"per rank, got G={G} (the rank IS the group)")
        if E % ep:
            raise ValueError(f"num_experts={E} not divisible by "
                             f"ep={ep} ({ep_axis!r})")
        if params["wi_gate"].shape[0] != E // ep:
            raise ValueError(
                f"expert-parallel moe_ffn wants the LOCAL expert block "
                f"({E // ep} of {E}), got params with "
                f"{params['wi_gate'].shape[0]} experts")

    xf = constrain(x.reshape(N, d), ("b", None))
    with jax.named_scope("router"):
        weights, experts, balance = _route(cfg, xf @ params["router"])

    with jax.named_scope("dispatch"):
        eg = constrain(experts.reshape(G, ng * k), ("b", None))
        wg = weights.reshape(G, ng * k)
        onehot = constrain(jax.nn.one_hot(eg, E, dtype=jnp.int32),
                           ("b", None, None))
        slot = (jnp.cumsum(onehot, axis=1) - 1) * onehot          # per-group
        flat_slot = slot.sum(-1)
        keep = flat_slot < cap
        dest = jnp.where(keep, eg * cap + flat_slot, E * cap)     # (G, ng*k)
        aux = {"balance": balance,
               "dropped": jnp.sum(~keep).astype(jnp.float32),
               "routed": jnp.asarray(keep.size, jnp.float32)}
        slot_choice = jax.vmap(
            lambda ds: _invert_slots(ds, E * cap))(dest)         # (G, E*cap)

        xg = constrain(xf.reshape(G, ng, d), ("b", None, None))
        buf = jax.vmap(lambda xs, ds, sc: _dispatch(xs, ds, sc, k))(
            xg, dest, slot_choice)                                # (G, E*cap, d)
        buf = constrain(buf.reshape(G, E, cap, d), ("b", "m", None, None))

    with jax.named_scope("experts"):
        if ep_axis is not None:
            from repro.core.collectives.api import all_to_all
            El = E // ep
            # dispatch: chunk s of the capacity buffer is the payload for ep
            # rank s (its expert block, GLOBAL expert order = rank-major)
            b = all_to_all(buf.reshape(ep, El * cap, d), ep_axis,
                           a2a_variant)
            b = b.reshape(ep, El, cap, d)   # row s: source rank s's tokens
            h_gate = jax.nn.silu(jnp.einsum("secd,edf->secf", b,
                                            params["wi_gate"]))
            h_up = jnp.einsum("secd,edf->secf", b, params["wi_up"])
            h_mid = (h_gate * h_up).astype(cdt)
            out_b = jnp.einsum("secf,efd->secd", h_mid, params["wo"])
            # combine: the reverse all-to-all returns each token's outputs to
            # its owner, re-assembling the (E, cap, d) buffer in global order
            out_flat = all_to_all(out_b.reshape(ep, El * cap, d), ep_axis,
                                  a2a_variant).reshape(G, E * cap, d)
        else:
            h_gate = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf,
                                            params["wi_gate"]))
            h_up = jnp.einsum("gecd,edf->gecf", buf, params["wi_up"])
            h_mid = constrain((h_gate * h_up).astype(cdt),
                              ("b", "m", None, None))
            out_buf = constrain(jnp.einsum("gecf,efd->gecd", h_mid,
                                           params["wo"]),
                                ("b", "m", None, None))
            out_flat = constrain(out_buf.reshape(G, E * cap, d),
                                 ("b", None, None))

    with jax.named_scope("combine"):
        out = jax.vmap(lambda of, ws, ds, sc: _combine(of, ws, ds, sc, k))(
            out_flat, wg, dest, slot_choice)                      # (G, ng, d)
        out = constrain(out, ("b", None, None)).reshape(N, d)

    if cfg.num_shared_experts:
        with jax.named_scope("shared"):
            out = out + mlp(params["shared"], xf, cfg.activation)
    return out.reshape(B, T, d), aux


def moe_decode_ffn(params, cfg: ModelConfig, x) -> jnp.ndarray:
    """Single-token path (B, 1, d): gather the k selected experts' weights per
    token instead of capacity dispatch — decode batches are tiny so the
    gather is cheap and drop-free."""
    B, _, d = x.shape
    xf = x.reshape(B, d)
    weights, experts, _ = _route(cfg, xf @ params["router"])       # (B,k)
    wg = params["wi_gate"][experts]                                # (B,k,d,ff)
    wu = params["wi_up"][experts]
    wo = params["wo"][experts]                                     # (B,k,ff,d)
    h = jax.nn.silu(jnp.einsum("bd,bkdf->bkf", xf, wg)) * jnp.einsum(
        "bd,bkdf->bkf", xf, wu)
    out = jnp.einsum("bkf,bkfd->bkd", h, wo)
    out = jnp.einsum("bkd,bk->bd", out, weights.astype(out.dtype))
    if cfg.num_shared_experts:
        out = out + mlp(params["shared"], xf, cfg.activation)
    return out.reshape(B, 1, d)
