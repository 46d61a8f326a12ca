"""Plain float32 reference of the Kimi Linear language model
[arXiv:2510.26692], for the configuration keys of its public config.json.

Each layer is ``x + mixer(norm(x))`` then ``x + FFN(norm(x))``.  The mixer
is MLA at the 1-based layers of ``linear_attn_config.full_attn_layers``
and Kimi Delta Attention (KDA) at the others.  The first
``first_k_dense_replace`` layers have a dense SwiGLU FFN of width
``intermediate_size``; the others route over ``router_experts`` SwiGLU
experts of width ``moe_intermediate_size`` (top ``num_experts_per_token``
by sigmoid score) and add ``num_shared_experts`` shared experts.

KDA, per head with d_k = d_v = ``linear_attn_config.head_dim``: q, k, v
are SiLU of a causal depthwise convolution (width
``short_conv_kernel_size``) of their projections; q and k are
L2-normalised, q scaled by d_k^-1/2; beta = sigmoid(W_beta x) per head;
the log-decay is -exp(A_log) softplus(W_f_up W_f_down x + dt_bias), one
per key channel; the state follows, one token at a time,

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T,
    o_t = S_t^T q_t,

and the layer returns W_o [RMSNorm_head(o) * sigmoid(W_g_up W_g_down x)].
This is the recurrence as written (a ``lax.scan`` over positions,
rematerialised in blocks of ``STEP_BLOCK`` steps), not a chunked form.
MLA without a query low-rank and without rotary embedding
(``mla_use_nope``): the ``qk_rope_head_dim`` parts of q and of the shared
key are kept as they are.

The chip holds a share of each MoE layer's experts: ``num_experts`` of
them, from index ``experts_offset``.  Routing runs over all
``router_experts``; the top-k weights are renormalised to sum to 1 and
scaled by ``routed_scaling_factor``; only the held experts' part of the
routed output is computed, and what the absent experts would add is left
out, as on the chip.

Departures from the published model, all shared with the configuration
as run (its ``assumed``, and the benchmark's PERF.md):
  * the batch's rows split into ``shards`` equal groups, one per data
    shard, and each group routes alone: an expert keeps at most
    ``capacity_factor * tokens * k / router_experts`` of the group's token
    choices, in token order, and drops the rest (published: not given);
  * the balance loss is ``aux_coef * E * sum_e f_e p_e`` over the top-1
    choices of each group, with p the sigmoid scores normalised over the
    E experts, averaged over the groups (published: not given here);
  * no score-correction bias in the top-k selection;
  * KDA's convolutions have no bias, its gate projections are low-rank
    through ``head_dim`` channels, the output gate without bias;
  * norms store their scale as a delta on 1.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from . import common as C

Q_BLOCK = 512     # query rows per attention block (memory, not semantics)
STEP_BLOCK = 64   # KDA steps per rematerialised block (memory, not semantics)


def layer_kinds(s) -> List[str]:
    full = set(s["linear_attn_config"]["full_attn_layers"])
    return ["mla" if i + 1 in full else "kda"
            for i in range(s["num_hidden_layers"])]


def _period(s) -> int:
    """Layers in one period of the stack: one full-attention layer and the
    KDA layers before it."""
    full = s["linear_attn_config"]["full_attn_layers"]
    return full[1] - full[0]


def layer_params(params, s, i):
    """Layer i in the program's stacked layout: one segment per leading
    dense layer, then a segment of whole periods (stacked over its
    repeats when more than one), then one of the remaining layers."""
    first = s["first_k_dense_replace"]
    if i < first:
        return params["stack"][i][0]
    p = _period(s)
    reps = (s["num_hidden_layers"] - first) // p
    j = i - first
    if j < reps * p:
        block = params["stack"][first][j % p]
        return jax.tree.map(lambda a: a[j // p], block) if reps > 1 else block
    return params["stack"][first + 1][j - reps * p]


# -- mixers ------------------------------------------------------------------

def _mla(p, s, x):
    B, T, _ = x.shape
    H = s["num_attention_heads"]
    nope, rope, vd = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    r = s["kv_lora_rank"]
    pos = jnp.arange(T)
    q = C.mm(x, p["wq"]).reshape(B, T, H, nope + rope)
    lat = C.mm(x, p["w_dkv"])
    c_kv = C.rmsnorm(lat[..., :r], p["kv_norm"]["scale"], s["rms_norm_eps"])
    k_rope = lat[..., r:]                                          # (B,T,rope)
    kv = C.mm(c_kv, p["w_ukv"]).reshape(B, T, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = 1.0 / jnp.sqrt(float(nope + rope))
    qb = min(Q_BLOCK, T)

    @jax.checkpoint
    def block(args):
        qn, qr, start = args                                       # (B,qb,H,.)
        sc = (C.einsum("bqhd,bkhd->bhqk", qn, k_nope)
              + C.einsum("bqhd,bkd->bhqk", qr, k_rope)) * scale
        qpos = start + jnp.arange(qb)
        sc = jnp.where(pos[None, :] <= qpos[:, None], sc, -jnp.inf)
        return C.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v)

    nb = T // qb
    split = lambda a: a.reshape(B, nb, qb, *a.shape[2:]).swapaxes(0, 1)
    out = jax.lax.map(block, (split(q[..., :nope]), split(q[..., nope:]),
                              jnp.arange(nb) * qb))
    out = out.swapaxes(0, 1).reshape(B, T, H * vd)
    return C.mm(out, p["wo"])


def _recurrence(q, k, v, g, beta):
    """o_t = S_t^T q_t for (B, T, H, .) inputs, one token at a time."""
    B, T, H, dk = q.shape

    def step(S, xs):
        qt, kt, vt, gt, bt = xs
        S = jnp.exp(gt)[..., None] * S
        u = bt[..., None] * (vt - C.einsum("bhk,bhkv->bhv", kt, S))
        S = S + C.einsum("bhk,bhv->bhkv", kt, u)
        return S, C.einsum("bhk,bhkv->bhv", qt, S)

    @jax.checkpoint
    def steps(S, xs):
        return jax.lax.scan(step, S, xs)

    nb = -(-T // STEP_BLOCK)
    blk = T // nb
    assert blk * nb == T, (T, STEP_BLOCK)
    xs = tuple(a.swapaxes(0, 1).reshape(nb, blk, *a.shape[:1], *a.shape[2:])
               for a in (q, k, v, g, beta))
    S0 = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(steps, S0, xs)                             # (nb,blk,B,H,dv)
    return o.reshape(T, B, H, -1).swapaxes(0, 1)


def _kda(p, s, x):
    B, T, _ = x.shape
    la = s["linear_attn_config"]
    H, dk, K = la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]
    hk = H * dk
    xs = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))

    def conv(w, stream):
        y = C.mm(xs, w)
        return jax.nn.silu(sum(y[:, i:i + T] * p["conv"][i, stream * hk:
                                                      (stream + 1) * hk]
                               for i in range(K)))

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = unit(conv(p["wq"], 0).reshape(B, T, H, dk)) * dk ** -0.5
    k = unit(conv(p["wk"], 1).reshape(B, T, H, dk))
    v = conv(p["wv"], 2).reshape(B, T, H, dk)
    beta = jax.nn.sigmoid(C.mm(x, p["w_beta"]))
    f = jax.nn.softplus(C.mm(C.mm(x, p["f_down"]), p["f_up"]) + p["b"])
    g = -jnp.exp(p["A_log"])[:, None] * f.reshape(B, T, H, dk)
    o = _recurrence(q, k, v, g, beta)
    o = C.rmsnorm(o, p["o_norm"]["scale"], s["rms_norm_eps"])
    gate = jax.nn.sigmoid(C.mm(C.mm(x, p["g_down"]), p["g_up"]))
    return C.mm((o.reshape(B, T, hk) * gate), p["wo"])


# -- FFNs --------------------------------------------------------------------

def _swiglu(p, x):
    return C.mm(jax.nn.silu(C.mm(x, p["wi_gate"])) * C.mm(x, p["wi_up"]),
                p["wo"])


def _balance(probs, top1, E):
    """Switch-style balance ``E * sum_e f_e p_e`` of one group's tokens."""
    return E * jnp.sum(jax.nn.one_hot(top1, E).mean(0) * probs.mean(0))


def _moe(p, s, x, shards=1):
    """Returns (held experts' routed output plus the shared experts, balance
    loss) for x (B, T, d), the B rows routed in ``shards`` equal groups of
    consecutive rows."""
    B, T, d = x.shape
    E, k = s["router_experts"], s["num_experts_per_token"]
    off, held = s["experts_offset"], s["num_experts"]
    n = B * T
    ng = n // shards
    xf = x.reshape(n, d)
    scores = jax.nn.sigmoid(C.mm(xf, p["router"]))                 # (n, E)
    w, ex = jax.lax.top_k(scores, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9) * s["routed_scaling_factor"]
    probs = scores / scores.sum(-1, keepdims=True)
    aux = sum(_balance(probs[g * ng:(g + 1) * ng], ex[g * ng:(g + 1) * ng, 0],
                       E) for g in range(shards)) / shards
    # capacity: a group's choices claim slots of their expert in token order
    cap = int(max(1, ng * k / E * s["capacity_factor"]))
    hot = jax.nn.one_hot(ex.reshape(shards, ng * k), E, dtype=jnp.int32)
    slot = jnp.sum((jnp.cumsum(hot, axis=1) - 1) * hot, axis=-1)  # (S, ng*k)
    keep = (slot < cap).reshape(n, k)
    comb = jnp.einsum("nk,nke->ne", w * keep,
                      jax.nn.one_hot(ex, E, dtype=jnp.float32))      # (n, E)
    comb = comb[:, off:off + held]                                 # held here

    @jax.checkpoint
    def expert(acc, args):
        wg, wu, wo, c = args
        y = C.mm(jax.nn.silu(C.mm(xf, wg)) * C.mm(xf, wu), wo)
        return acc + c[:, None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(xf),
                          (p["wi_gate"], p["wi_up"], p["wo"], comb.T))
    if s["num_shared_experts"]:
        out = out + _swiglu(p["shared"], xf)
    return out.reshape(B, T, d), aux


def nll_sum(params, sizes, tokens, shards=1):
    """(summed next-token NLL, predicted positions, weighted balance loss),
    the MoE layers routing ``shards`` equal groups of rows alone."""
    eps = sizes["rms_norm_eps"]
    mixers = {"mla": _mla, "kda": _kda}
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    aux = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(layer_kinds(sizes)):
        p = layer_params(params, sizes, i)
        x = x + jax.checkpoint(lambda q, h, f=mixers[kind]: f(q, sizes, h))(
            p["mixer"], C.rmsnorm(x, p["norm1"]["scale"], eps))
        h = C.rmsnorm(x, p["norm2"]["scale"], eps)
        if i < sizes["first_k_dense_replace"]:
            x = x + jax.checkpoint(_swiglu)(p["ffn"], h)
        else:
            y, a = jax.checkpoint(lambda q, h_: _moe(q, sizes, h_, shards))(
                p["ffn"], h)
            x, aux = x + y, aux + a
    h = C.rmsnorm(x, params["final_norm"]["scale"], eps)
    total, count = C.next_token_nll(h, params["lm_head"]["table"], tokens)
    return total, count, sizes["aux_coef"] * aux


# -- operation and byte counts (rules in flops.py) -------------------------------

def kda_params(s) -> int:
    """Matmul parameters of one KDA layer: q, k, v, output, the two
    low-rank gate projections and beta's."""
    d, la = s["hidden_size"], s["linear_attn_config"]
    H, dk = la["num_heads"], la["head_dim"]
    hk = H * dk
    return 4 * d * hk + 2 * (d * dk + dk * hk) + d * H


def kda_forward_flops(s) -> float:
    """Forward operations of one KDA layer per token: its matmuls, and the
    recurrence's q.S, k.S and k (x) u products per head (the short
    convolution and the elementwise gates are not counted)."""
    la = s["linear_attn_config"]
    return 2.0 * kda_params(s) + 6.0 * la["num_heads"] * la["head_dim"] ** 2


def kda_layers(s) -> int:
    return layer_kinds(s).count("kda")


def kda_step_flops(s, tokens: float) -> float:
    """Forward and backward operations of every KDA layer for ``tokens``."""
    return 3.0 * kda_layers(s) * kda_forward_flops(s) * tokens


def kda_step_bytes(s, tokens: float, width: int = 2) -> float:
    """Bytes every KDA layer must move for ``tokens`` in a training step,
    in ``width``-byte words: its weights read forward and backward and
    their gradient written; its input read forward and again backward,
    its output written, the output's cotangent read and the input's
    written.  Nothing recomputed, and no state leaves the chip."""
    return kda_layers(s) * width * (3.0 * kda_params(s)
                                    + 5.0 * tokens * s["hidden_size"])


def forward_flops(s, seq: int) -> float:
    """Forward operations per token (rules in ``flops.py``): routed experts
    count at ``top_k * held / router_experts`` of one expert, the share of
    a token's choices this chip's experts compute on average."""
    d, H = s["hidden_size"], s["num_attention_heads"]
    nope, rope, vd = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    r = s["kv_lora_rank"]
    kinds = layer_kinds(s)
    first = s["first_k_dense_replace"]
    E, k, held = s["router_experts"], s["num_experts_per_token"], s["num_experts"]
    ff, dff = s["moe_intermediate_size"], s["intermediate_size"]
    if s.get("q_lora_rank"):
        raise ValueError("query low-rank projections are not counted here")
    mla = d * H * (nope + rope) + d * (r + rope) + r * H * (nope + vd) + H * vd * d
    dense = 3 * d * dff
    moe = d * E + 3 * d * ff * s["num_shared_experts"] + 3 * d * ff * k * held / E
    head = s["vocab_size"] * d
    n_mla = kinds.count("mla")
    params = (n_mla * mla + first * dense + (len(kinds) - first) * moe + head)
    ctx = (seq + 1) / 2.0
    mixing = n_mla * 2 * H * (nope + rope + vd) * ctx
    return 2.0 * params + mixing + kda_layers(s) * kda_forward_flops(s)
