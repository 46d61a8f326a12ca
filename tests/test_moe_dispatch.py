"""The MoE capacity dispatch and combine (``moe.moe_ffn``) move rows by
gathers in both directions.  Checked against the scatter formulation they
replace, kept here as the reference: the same outputs, drop counts and
gradients, and no scatter of model-width rows anywhere in the grad
program."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import moe
from repro.models.layers import mlp

D = 24          # model width, distinct from every other dim below


def _cfg(capacity_factor):
    return ModelConfig(name="t", family="qwen3", num_layers=1, d_model=D,
                       num_heads=2, num_kv_heads=2, d_ff=32, vocab_size=64,
                       num_experts=8, top_k=3, moe_d_ff=20,
                       capacity_factor=capacity_factor, num_shared_experts=1)


def _params(cfg):
    E, ff = cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    n = lambda i, s, sc: jax.random.normal(ks[i], s) * sc   # noqa: E731
    return {"router": n(0, (D, E), 1.0),
            "wi_gate": n(1, (E, D, ff), 0.3), "wi_up": n(2, (E, D, ff), 0.3),
            "wo": n(3, (E, ff, D), 0.3),
            "shared": {"wi_gate": n(4, (D, ff), 0.3),
                       "wi_up": n(5, (D, ff), 0.3), "wo": n(6, (ff, D), 0.3)}}


def _x():
    return jax.random.normal(jax.random.PRNGKey(1), (4, 8, D))


def _scatter_moe_ffn(params, cfg, x, groups):
    """The scatter formulation: each choice's token row scattered into its
    slot, each slot's output gathered back, weighted and scatter-added into
    its token."""
    B, T, d = x.shape
    N, E, k, G = B * T, cfg.num_experts, cfg.top_k, groups
    ng = N // G
    cap = int(max(1, ng * k / E * cfg.capacity_factor))
    xf = x.reshape(N, d)
    weights, experts, balance = moe._route(cfg, xf @ params["router"])
    eg = experts.reshape(G, ng * k)
    wg = weights.reshape(G, ng * k)
    onehot = jax.nn.one_hot(eg, E, dtype=jnp.int32)
    flat_slot = ((jnp.cumsum(onehot, axis=1) - 1) * onehot).sum(-1)
    keep = flat_slot < cap
    dest = jnp.where(keep, eg * cap + flat_slot, E * cap)
    tok_idx = jnp.repeat(jnp.arange(ng), k)
    src = jnp.take(xf.reshape(G, ng, d), tok_idx, axis=1)

    def scatter_one(s, idx):
        return jnp.zeros((E * cap + 1, d), x.dtype).at[idx].set(s)[: E * cap]

    buf = jax.vmap(scatter_one)(src, dest).reshape(G, E, cap, d)
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, params["wi_gate"]))
    h = h * jnp.einsum("gecd,edf->gecf", buf, params["wi_up"])
    out_flat = jnp.einsum("gecf,efd->gecd", h,
                          params["wo"]).reshape(G, E * cap, d)

    def gather_one(flat, idx, kp):
        g = jnp.take(flat, jnp.minimum(idx, E * cap - 1), axis=0)
        return jnp.where(kp[:, None], g, 0.0)

    contrib = jax.vmap(gather_one)(out_flat, dest, keep) * wg[..., None]
    out = jax.vmap(
        lambda c: jnp.zeros((ng, d), x.dtype).at[tok_idx].add(c))(contrib)
    out = out.reshape(N, d) + mlp(params["shared"], xf, cfg.activation)
    aux = {"balance": balance,
           "dropped": jnp.sum(~keep).astype(jnp.float32),
           "routed": jnp.asarray(keep.size, jnp.float32)}
    return out.reshape(B, T, d), aux


def _value_and_grad(ffn):
    def loss(params, x):
        out, aux = ffn(params, x)
        return jnp.sum(jnp.sin(out)) + aux["balance"], (out, aux)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("capacity_factor", [0.25, 8.0])
def test_gather_dispatch_matches_scatter_reference(capacity_factor, groups):
    cfg = _cfg(capacity_factor)
    params, x = _params(cfg), _x()
    (_, (out, aux)), grads = _value_and_grad(
        lambda p, v: moe.moe_ffn(p, cfg, v, groups=groups))(params, x)
    (_, (out_r, aux_r)), grads_r = _value_and_grad(
        lambda p, v: _scatter_moe_ffn(p, cfg, v, groups))(params, x)

    assert float(aux["dropped"]) == float(aux_r["dropped"])
    assert float(aux["routed"]) == float(aux_r["routed"])
    if capacity_factor < 1:
        assert float(aux["dropped"]) > 0          # dropped choices
    else:
        assert float(aux["dropped"]) == 0         # most slots empty
    np.testing.assert_allclose(out, out_r, rtol=1e-5, atol=1e-5)
    (gp, gx), (gp_r, gx_r) = grads, grads_r
    np.testing.assert_allclose(gx, gx_r, rtol=1e-5, atol=1e-5)
    for name in ("router", "wi_gate", "wi_up", "wo"):
        assert float(jnp.abs(gp[name]).max()) > 0, name
        np.testing.assert_allclose(gp[name], gp_r[name], rtol=1e-5,
                                   atol=1e-5, err_msg=name)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("groups", [1, 2])
def test_moe_grad_program_scatters_no_rows(groups):
    """Only the int32 slot map is scattered; every model-width row moves by
    a gather, in the forward and in the backward."""
    cfg = _cfg(0.5)
    params, x = _params(cfg), _x().astype(jnp.bfloat16)
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)

    def loss(params, x):
        out, aux = moe.moe_ffn(params, cfg, x, groups=groups)
        return jnp.sum(out.astype(jnp.float32) ** 2) + aux["balance"]

    eqns = list(_eqns(jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1)))(params, x).jaxpr))
    scatters = [e for e in eqns if e.primitive.name.startswith("scatter")]
    row_scatters = [e for e in scatters
                    if e.invars[2].aval.shape[-1:] == (D,)]
    assert not row_scatters, [str(e) for e in row_scatters]
    assert any(e.invars[0].aval.dtype == jnp.int32 for e in scatters)
    row_gathers = [e for e in eqns if e.primitive.name == "gather"
                   and e.outvars[0].aval.shape[-1:] == (D,)]
    assert len(row_gathers) >= 4        # dispatch and combine, both ways


def _share_cfg(**kw):
    import dataclasses
    return dataclasses.replace(_cfg(1.0), num_experts=32, top_k=4,
                               router_act="sigmoid", routed_scale=2.446, **kw)


def _held(params, off, n):
    return dict(params, **{k: params[k][off:off + n]
                           for k in ("wi_gate", "wi_up", "wo")})


@pytest.mark.parametrize("groups", [1, 2])
def test_held_shares_add_up_to_the_whole_layer(groups):
    """The 8-expert shares of all E/8 offsets, the shared expert (which
    every chip computes alike) counted once, add up to the whole layer;
    their routed and dropped choices add up to the whole layer's too."""
    import dataclasses
    cfg = _share_cfg()
    E, held = cfg.num_experts, 8
    k = jax.random.split(jax.random.PRNGKey(3), 7)
    params = dict(_params(_cfg(1.0)), router=jax.random.normal(k[0], (D, E)),
                  **{n: jax.random.normal(k[i + 1], s) * 0.3 for i, (n, s) in
                     enumerate((("wi_gate", (E, D, 20)), ("wi_up", (E, D, 20)),
                                ("wo", (E, 20, D))))})
    x = _x()
    whole, aux = moe.moe_ffn(params, cfg, x, groups=groups)
    shared = mlp(params["shared"], x, cfg.activation)
    total, routed, dropped = shared, 0.0, 0.0
    for off in range(0, E, held):
        c = dataclasses.replace(cfg, experts_held=held, experts_offset=off)
        out, a = moe.moe_ffn(_held(params, off, held), c, x, groups=groups)
        total = total + (out - shared)
        routed += float(a["routed"])
        dropped += float(a["dropped"])
        np.testing.assert_allclose(float(a["balance"]), float(aux["balance"]),
                                   rtol=1e-6)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    assert routed == float(aux["routed"]) == x.shape[0] * x.shape[1] * 4
    assert dropped == float(aux["dropped"]) > 0


def test_held_share_decode_matches_dispatch():
    """The single-token decode path computes the same held share."""
    import dataclasses
    cfg = dataclasses.replace(_share_cfg(), experts_held=8, experts_offset=8,
                              capacity_factor=64.0)
    params = _held(dict(_params(_cfg(1.0)), router=jax.random.normal(
        jax.random.PRNGKey(4), (D, 32)), **{n: jnp.tile(v, (4,) + (1,) * (
            v.ndim - 1)) for n, v in _params(_cfg(1.0)).items()
            if n in ("wi_gate", "wi_up", "wo")}), 8, 8)
    x = _x()[:, :1]
    want, _ = moe.moe_ffn(params, cfg, x, groups=1)
    got = moe.moe_decode_ffn(params, cfg, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


def test_sigmoid_router_weights_sum_to_routed_scale():
    cfg = _share_cfg()
    logits = jax.random.normal(jax.random.PRNGKey(5), (64, cfg.num_experts))
    w, ex, bal = moe._route(cfg, logits)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.446, rtol=1e-6)
    # the top 4 by sigmoid score, in score order
    np.testing.assert_array_equal(np.asarray(ex),
                                  np.argsort(-np.asarray(logits), -1)[:, :4])
    assert 0.0 < float(bal) and bool(jnp.isfinite(bal))
