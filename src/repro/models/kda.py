"""Kimi Delta Attention (KDA), the linear-attention mixer of Kimi Linear
[arXiv:2510.26692]: a gated delta rule with one decay per key channel.

For each head, with q, k L2-normalised (q also scaled by d_k^-1/2),
beta_t in (0, 1) and a per-channel decay alpha_t = exp(g_t), g_t <= 0:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

Training and prefill run the chunked form (``chunk_kda``): inside a chunk
of C tokens the delta rule's WY/UT form, across chunks a ``lax.scan`` over
the (d_k, d_v) state, its body rematerialised so the backward pass keeps
one state per chunk.  Every decay factor it forms is exp of a difference
of cumulative log-decays that is <= 0, so no decay overflows float32:
pairs inside a sub-chunk take the difference directly, pairs across
sub-chunks split it at the two sub-chunks' boundaries.  Decode is one step
of the recurrence (``kda_step``) on a float32 state.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import ParamDesc, norm_desc, rmsnorm

CHUNK = 64          # tokens per chunk of the chunked form
SUB = 16            # sub-chunk length: pairs inside one take direct decays
HIGHEST = jax.lax.Precision.HIGHEST


def kda_desc(cfg: ModelConfig) -> Dict[str, ParamDesc]:
    """num_heads heads of head_dim; the decay and output-gate projections
    are low-rank through head_dim channels."""
    d, H, dk = cfg.d_model, cfg.num_heads, cfg.hd
    hk = H * dk
    return {
        "wq": ParamDesc((d, hk), ("embed", "heads")),
        "wk": ParamDesc((d, hk), ("embed", "heads")),
        "wv": ParamDesc((d, hk), ("embed", "heads")),
        "conv": ParamDesc((cfg.ssm_conv, 3 * hk), (None, "heads")),
        "w_beta": ParamDesc((d, H), ("embed", None)),
        "f_down": ParamDesc((d, dk), ("embed", None)),
        "f_up": ParamDesc((dk, hk), (None, "heads")),
        "b": ParamDesc((hk,), ("heads",), "zeros"),
        "A_log": ParamDesc((H,), (None,), "zeros"),
        "g_down": ParamDesc((d, dk), ("embed", None)),
        "g_up": ParamDesc((dk, hk), (None, "heads")),
        "o_norm": norm_desc(dk),
        "wo": ParamDesc((hk, d), ("heads", "embed")),
    }


# ---------------------------------------------------------------------------
# The recurrence: one step, and the chunked form
# ---------------------------------------------------------------------------

def kda_step(S, q, k, v, g, beta):
    """One token. S: (B, H, dk, dv) f32; q, k, g: (B, H, dk); v: (B, H, dv);
    beta: (B, H).  Returns (S_t, o_t)."""
    S = jnp.exp(g)[..., None] * S
    u = beta[..., None] * (v - jnp.einsum("bhk,bhkv->bhv", k, S))
    S = S + k[..., None] * u[..., None, :]
    return S, jnp.einsum("bhk,bhkv->bhv", q, S)


def _unit_lower_inverse(A):
    """(I + A)^-1 for strictly lower-triangular A (..., C, C), C a power of
    two, by doubling the inverted diagonal blocks: [[X1, 0], [-X2 A21 X1,
    X2]] inverts [[I + A11, 0], [A21, I + A22]].  Matmuls only."""
    C = A.shape[-1]
    lead = A.shape[:-2]
    X = jnp.ones(lead + (C, 1, 1), A.dtype)
    b = 1
    while b < C:
        n = C // (2 * b)
        pairs = X.reshape(lead + (n, 2, b, b))
        X1, X2 = pairs[..., 0, :, :], pairs[..., 1, :, :]
        # the diagonal 2b-blocks of A, by a mask (a gather is slow on TPU)
        blocks = jnp.sum(A.reshape(lead + (n, 2 * b, n, 2 * b))
                         * jnp.eye(n, dtype=A.dtype)[:, None, :, None], axis=-2)
        L = -jnp.matmul(jnp.matmul(X2, blocks[..., b:, :b], precision=HIGHEST),
                        X1, precision=HIGHEST)
        X = jnp.concatenate([
            jnp.concatenate([X1, jnp.zeros_like(X1)], axis=-1),
            jnp.concatenate([L, X2], axis=-1)], axis=-2)
        b *= 2
    return X.reshape(lead + (C, C))


def _pair_products(q, k, G, sub):
    """Two (..., C, C) matrices, 0 above the diagonal: sum_c a_t[c] k_s[c]
    exp(G_t[c] - G_s[c]) for a = k (s < t) and for a = q (s <= t).  q, k,
    G: (..., C, dk); G is the cumulative log-decay, non-increasing along C."""
    *lead, C, dk = q.shape
    n = C // sub
    q, k, G = (x.reshape(*lead, n, sub, dk) for x in (q, k, G))
    t = jnp.arange(sub)
    # inside a sub-chunk: the decays of each pair, taken directly
    tri = t[:, None] >= t[None, :]
    decays = jnp.exp(jnp.where(tri[:, :, None],
                               G[..., :, None, :] - G[..., None, :, :],
                               -jnp.inf)) * k[..., None, :, :]
    # across sub-chunks i > j: t -> start of i -> end of j -> s, each <= 1
    first, last = G[..., 0, :], G[..., -1, :]                 # (..., n, dk)
    i = jnp.arange(n)
    mid = jnp.exp(jnp.where((i[:, None] > i[None, :])[:, :, None],
                            first[..., :, None, :] - last[..., None, :, :],
                            -jnp.inf))                        # (..., i, j, dk)
    cols = k * jnp.exp(G[..., -1:, :] - G)
    to_start = jnp.exp(G - G[..., :1, :])
    eye = jnp.eye(n)[:, :, None, None]

    def products(a, inner_mask):
        inner = jnp.sum(a[..., :, None, :] * decays, axis=-1) * inner_mask
        cross = jnp.einsum("...ijtc,...jsc->...ijts",
                           (a * to_start)[..., :, None, :, :]
                           * mid[..., None, :], cols)         # (..., i, j, t, s)
        blocks = cross + inner[..., :, None, :, :] * eye
        return jnp.swapaxes(blocks, -3, -2).reshape(*lead, C, C)

    return products(k, t[:, None] > t[None, :]), products(q, 1.0)


def _chunk(S, q, k, v, g, beta, sub):
    """One chunk from state S.  q, k, g: (B, H, C, dk); v: (B, H, C, dv);
    beta: (B, H, C).  Returns (state after the chunk, outputs (B, H, C, dv))."""
    G = jnp.cumsum(g, axis=-2)                                # Gamma_t <= 0
    kk, qk = _pair_products(q, k, G, sub)
    T = _unit_lower_inverse(beta[..., None] * kk) * beta[..., None, :]
    decay = jnp.exp(G)                                        # T: (I + A)^-1 Diag(beta)
    W = T @ (k * decay)
    U = T @ v - W @ S                                         # (B, H, C, dv)
    o = (q * decay) @ S + qk @ U
    k_end = k * jnp.exp(G[..., -1:, :] - G)
    S = decay[..., -1, :, None] * S + jnp.swapaxes(k_end, -1, -2) @ U
    return S, o


def chunk_kda(q, k, v, g, beta, chunk: int = CHUNK,
              state: Optional[jnp.ndarray] = None, out_dtype=None):
    """The recurrence over a sequence, chunk by chunk.  q, k, g: (B, T, H,
    dk); v: (B, T, H, dv); beta: (B, T, H); ``state`` (B, H, dk, dv) or
    zeros.  T is padded to a multiple of the chunk with tokens that leave
    the state as it is (beta 0, no decay).  Returns (o (B, T, H, dv),
    final state (B, H, dk, dv) float32)."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = min(chunk, 1 << (T - 1).bit_length())
    if C & (C - 1):
        raise ValueError(f"chunk {chunk} is not a power of two")
    sub = min(SUB, C)
    pad = -T % C
    N = (T + pad) // C

    def split(a):
        a = a.astype(jnp.float32)
        a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        a = a.reshape((B, N, C) + a.shape[2:])
        return jnp.moveaxis(a, (1, 3), (0, 2))                # (N, B, H, C, ...)

    if state is None:
        state = jnp.zeros((B, H, dk, dv), jnp.float32)
    body = jax.checkpoint(functools.partial(_chunk, sub=sub))
    S, o = jax.lax.scan(
        lambda s, xs: body(s, *xs), state.astype(jnp.float32),
        (split(q), split(k), split(v), split(g), split(beta)))
    o = o.astype(out_dtype or q.dtype)                        # (N, B, H, C, dv)
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(B, N * C, H, dv)
    return o[:, :T], S


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------

def _short_conv(w, x, tail):
    """Causal depthwise convolution over time. w: (K, c); x: (B, T, c);
    tail: (B, K-1, c), the inputs before x."""
    K, T = w.shape[0], x.shape[1]
    xs = jnp.concatenate([tail, x], axis=1)
    return sum(xs[:, i:i + T] * w[i] for i in range(K))


def _l2norm(x, eps=1e-6):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def _gates(params, cfg: ModelConfig, x, q, k):
    """Normalised q and k, beta and the log-decay g, float32."""
    B, T, _ = x.shape
    H, dk = cfg.num_heads, cfg.hd
    q = _l2norm(q.reshape(B, T, H, dk)) * dk ** -0.5
    k = _l2norm(k.reshape(B, T, H, dk))
    beta = jax.nn.sigmoid((x @ params["w_beta"]).astype(jnp.float32))
    f = ((x @ params["f_down"]) @ params["f_up"]).astype(jnp.float32)
    f = jax.nn.softplus(f + params["b"].astype(jnp.float32))
    A = jnp.exp(params["A_log"].astype(jnp.float32))
    g = -A[:, None] * f.reshape(B, T, H, dk)
    return q, k, beta, g


def _output(params, cfg: ModelConfig, x, o):
    """W_o [RMSNorm_head(o) * sigmoid(W_g_up W_g_down x)]."""
    B, T, H, dv = o.shape
    gate = (x @ params["g_down"]) @ params["g_up"]
    o = rmsnorm(params["o_norm"], o, eps=cfg.norm_eps)
    o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).reshape(B, T, H, dv)
    return o.astype(x.dtype).reshape(B, T, H * dv) @ params["wo"]


def kda_forward(params, cfg: ModelConfig, x, return_state: bool = False):
    """x: (B, T, d) -> (B, T, d) [, decode state].  Runs under the block's
    scope, its parts under ``proj``, ``conv``, ``gates``, ``chunk`` and
    ``out``."""
    B, T, _ = x.shape
    H, dk, K = cfg.num_heads, cfg.hd, cfg.ssm_conv
    hk = H * dk
    with jax.named_scope("proj"):
        raw = [x @ params["wq"], x @ params["wk"], x @ params["wv"]]
    with jax.named_scope("conv"):
        tail = jnp.zeros((B, K - 1, hk), x.dtype)
        q, k, v = (jax.nn.silu(_short_conv(params["conv"][:, i * hk:(i + 1) * hk],
                                           raw[i], tail))
                   for i in range(3))
    with jax.named_scope("gates"):
        q, k, beta, g = _gates(params, cfg, x, q, k)
    with jax.named_scope("chunk"):
        o, S = chunk_kda(q, k, v.reshape(B, T, H, dk), g, beta,
                         chunk=CHUNK, out_dtype=x.dtype)
    with jax.named_scope("out"):
        out = _output(params, cfg, x, o)
    if not return_state:
        return out
    seen = jnp.concatenate([jnp.zeros((B, K - 1, 3 * hk), x.dtype),
                            jnp.concatenate(raw, -1)], axis=1)
    return out, {"S": S, "conv": seen[:, -(K - 1):]}


def init_kda_state(cfg: ModelConfig, batch: int, dtype):
    H, dk, K = cfg.num_heads, cfg.hd, cfg.ssm_conv
    return {"S": jax.ShapeDtypeStruct((batch, H, dk, dk), jnp.float32),
            "conv": jax.ShapeDtypeStruct((batch, K - 1, 3 * H * dk), dtype)}


def kda_decode(params, cfg: ModelConfig, x, state) -> Tuple[jnp.ndarray, Dict]:
    """One token. x: (B, 1, d); state: {'S', 'conv'}."""
    B = x.shape[0]
    H, dk = cfg.num_heads, cfg.hd
    hk = H * dk
    raw = jnp.concatenate([x @ params["wq"], x @ params["wk"],
                           x @ params["wv"]], axis=-1)         # (B, 1, 3hk)
    window = jnp.concatenate([state["conv"], raw], axis=1)     # (B, K, 3hk)
    qkv = jax.nn.silu(jnp.einsum("bkc,kc->bc", window, params["conv"]))
    q, k, beta, g = _gates(params, cfg, x, qkv[:, None, :hk],
                           qkv[:, None, hk:2 * hk])
    v = qkv[:, 2 * hk:].reshape(B, H, dk).astype(jnp.float32)
    S, o = kda_step(state["S"], q[:, 0], k[:, 0], v, g[:, 0], beta[:, 0])
    out = _output(params, cfg, x, o[:, None].astype(x.dtype))
    return out, {"S": S, "conv": window[:, 1:]}
