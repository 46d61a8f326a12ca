"""Plain float32 reference of the DeepSeek-V2 language model
[arXiv:2405.04434], for the configuration keys of its public config.json.

Each layer is ``x + MLA(norm(x))`` then ``x + FFN(norm(x))``; the first
``first_k_dense_replace`` layers have a dense SwiGLU FFN of width
``intermediate_size``, the others a mixture of ``n_routed_experts`` SwiGLU
experts of width ``moe_intermediate_size`` (top ``num_experts_per_tok`` by
softmax score) plus ``n_shared_experts`` shared experts applied to every
token.  MLA without a query low-rank: queries come straight from the hidden
state; keys and values from an RMS-normed latent of rank ``kv_lora_rank``
plus one rotary key of ``qk_rope_head_dim`` shared by all heads.

Departures from the published model, all shared with the configuration
as run (and listed in the benchmark's PERF.md):
  * the top-k weights are renormalised to sum to 1 (published:
    ``norm_topk_prob`` false);
  * the batch's rows split into ``shards`` equal groups, one per data
    shard (device ``d`` holds rows ``[d R, (d+1) R)``), and each group
    routes alone: an expert keeps at most ``capacity_factor * tokens * k
    / E`` of the group's token choices, in token order, and drops the
    rest (published: device-level dropping at a factor not given);
  * the balance loss is ``aux_coef * E * sum_e f_e p_e`` over the top-1
    choices of each group, averaged over the groups (published: expert-,
    device- and communication-level losses);
  * rotary embedding in the halves layout without YaRN scaling or its
    attention-scale correction (published: YaRN, factor 40);
  * norms store their scale as a delta on 1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as C

Q_BLOCK = 512     # query rows per attention block (memory, not semantics)


def layer_kinds(sizes):
    return ["dense" if i < sizes["first_k_dense_replace"] else "moe"
            for i in range(sizes["num_hidden_layers"])]


def layer_params(params, sizes, i):
    """Layer i in the stacked layout: one segment per leading dense layer,
    then one segment holding the MoE layers, stacked when more than one."""
    first = sizes["first_k_dense_replace"]
    if i < first:
        return params["stack"][i][0]
    reps = sizes["num_hidden_layers"] - first
    block = params["stack"][first][0]
    if reps > 1:
        return jax.tree.map(lambda a: a[i - first], block)
    return block


def _mla(p, s, x):
    B, T, _ = x.shape
    H = s["num_attention_heads"]
    nope, rope, vd = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    r = s["kv_lora_rank"]
    pos = jnp.arange(T)
    q = C.mm(x, p["wq"]).reshape(B, T, H, nope + rope)
    q_nope, q_rope = q[..., :nope], C.rope(q[..., nope:], pos, s["rope_theta"])
    lat = C.mm(x, p["w_dkv"])
    c_kv = C.rmsnorm(lat[..., :r], p["kv_norm"]["scale"], s["rms_norm_eps"])
    k_rope = C.rope(lat[..., None, r:], pos, s["rope_theta"])    # (B,T,1,rope)
    kv = C.mm(c_kv, p["w_ukv"]).reshape(B, T, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = 1.0 / jnp.sqrt(float(nope + rope))
    qb = min(Q_BLOCK, T)

    @jax.checkpoint
    def block(args):
        qn, qr, start = args                                       # (B,qb,H,.)
        sc = (C.einsum("bqhd,bkhd->bhqk", qn, k_nope)
              + C.einsum("bqhd,bkd->bhqk", qr, k_rope[:, :, 0])) * scale
        qpos = start + jnp.arange(qb)
        sc = jnp.where(pos[None, :] <= qpos[:, None], sc, -jnp.inf)
        return C.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v)

    nb = T // qb
    split = lambda a: a.reshape(B, nb, qb, *a.shape[2:]).swapaxes(0, 1)
    out = jax.lax.map(block, (split(q_nope), split(q_rope),
                              jnp.arange(nb) * qb))
    out = out.swapaxes(0, 1).reshape(B, T, H * vd)
    return C.mm(out, p["wo"])


def _swiglu(p, x):
    return C.mm(jax.nn.silu(C.mm(x, p["wi_gate"])) * C.mm(x, p["wi_up"]),
                p["wo"])


def _balance(probs, top1, E):
    """Switch-style balance ``E * sum_e f_e p_e`` of one group's tokens."""
    return E * jnp.sum(jax.nn.one_hot(top1, E).mean(0) * probs.mean(0))


def _moe(p, s, x, shards=1):
    """Returns (output, balance loss) for x (B, T, d), the B rows routed
    in ``shards`` equal groups of consecutive rows."""
    B, T, d = x.shape
    E, k = s["n_routed_experts"], s["num_experts_per_tok"]
    n = B * T
    ng = n // shards
    xf = x.reshape(n, d)
    probs = jax.nn.softmax(C.mm(xf, p["router"]), axis=-1)         # (n, E)
    w, ex = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    aux = sum(_balance(probs[g * ng:(g + 1) * ng], ex[g * ng:(g + 1) * ng, 0],
                       E) for g in range(shards)) / shards
    # capacity: a group's choices claim slots of their expert in token order
    cap = int(max(1, ng * k / E * s["capacity_factor"]))
    hot = jax.nn.one_hot(ex.reshape(shards, ng * k), E, dtype=jnp.int32)
    slot = jnp.sum((jnp.cumsum(hot, axis=1) - 1) * hot, axis=-1)  # (S, ng*k)
    keep = (slot < cap).reshape(n, k)
    comb = jnp.einsum("nk,nke->ne", w * keep,
                      jax.nn.one_hot(ex, E, dtype=jnp.float32))      # (n, E)

    @jax.checkpoint
    def expert(acc, args):
        wg, wu, wo, c = args
        y = C.mm(jax.nn.silu(C.mm(xf, wg)) * C.mm(xf, wu), wo)
        return acc + c[:, None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(xf),
                          (p["wi_gate"], p["wi_up"], p["wo"], comb.T))
    if s["n_shared_experts"]:
        out = out + _swiglu(p["shared"], xf)
    return out.reshape(B, T, d), aux


def nll_sum(params, sizes, tokens, shards=1):
    """(summed next-token NLL, predicted positions, weighted balance loss),
    the MoE layers routing ``shards`` equal groups of rows alone."""
    eps = sizes["rms_norm_eps"]
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    aux = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(layer_kinds(sizes)):
        p = layer_params(params, sizes, i)
        x = x + jax.checkpoint(lambda q, h: _mla(q, sizes, h))(
            p["mixer"], C.rmsnorm(x, p["norm1"]["scale"], eps))
        h = C.rmsnorm(x, p["norm2"]["scale"], eps)
        if kind == "dense":
            x = x + jax.checkpoint(_swiglu)(p["ffn"], h)
        else:
            y, a = jax.checkpoint(lambda q, h_: _moe(q, sizes, h_, shards))(
                p["ffn"], h)
            x, aux = x + y, aux + a
    h = C.rmsnorm(x, params["final_norm"]["scale"], eps)
    total, count = C.next_token_nll(h, params["lm_head"]["table"], tokens)
    return total, count, sizes["aux_coef"] * aux


def forward_flops(s, seq: int) -> float:
    """Forward operations per token (rules in ``flops.py``)."""
    d, H = s["hidden_size"], s["num_attention_heads"]
    nope, rope, vd = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    r = s["kv_lora_rank"]
    L, first = s["num_hidden_layers"], s["first_k_dense_replace"]
    E, k = s["n_routed_experts"], s["num_experts_per_tok"]
    ff, dff = s["moe_intermediate_size"], s["intermediate_size"]
    if s.get("q_lora_rank"):
        raise ValueError("query low-rank projections are not counted here")
    mla = d * H * (nope + rope) + d * (r + rope) + r * H * (nope + vd) + H * vd * d
    dense = 3 * d * dff
    moe = d * E + 3 * d * ff * s["n_shared_experts"] + 3 * d * ff * k
    head = s["vocab_size"] * d
    params = L * mla + first * dense + (L - first) * moe + head
    ctx = (seq + 1) / 2.0
    mixing = L * 2 * H * (nope + rope + vd) * ctx
    return 2.0 * params + mixing
