"""KDA (models/kda.py): the chunked form against the token-by-token
recurrence, values and gradients, at several chunk sizes and at decays
steep enough that a factorised exp(Gamma_i) exp(-Gamma_j) would overflow."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import Model, kda

RNG = jax.random.PRNGKey(0)


def naive(q, k, v, g, beta, state=None):
    """The recurrence one token at a time (B, T, H, .) -> (o, S)."""
    B, T, H, dk = q.shape
    S = (jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32) if state is None
         else state)
    S, o = jax.lax.scan(lambda s, xs: kda.kda_step(s, *xs), S,
                        tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v, g, beta)))
    return jnp.swapaxes(o, 0, 1), S


def inputs(key, B=2, T=40, H=3, dk=16, dv=8, decay=1.0):
    ks = jax.random.split(key, 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    # per-channel log-decays around -decay, some channels far steeper
    g = -decay * jnp.exp(0.5 * jax.random.normal(ks[3], (B, T, H, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


@pytest.mark.parametrize("chunk", [4, 8, 16, 64])
@pytest.mark.parametrize("decay", [0.05, 1.0, 5.0])
def test_chunked_matches_recurrence(chunk, decay):
    q, k, v, g, beta = inputs(RNG, decay=decay)
    o1, s1 = kda.chunk_kda(q, k, v, g, beta, chunk=chunk)
    o2, s2 = naive(q, k, v, g, beta)
    assert bool(jnp.all(jnp.isfinite(o1)))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("chunk", [8, 32])
@pytest.mark.parametrize("decay", [1.0, 5.0])
def test_chunked_gradients_match_recurrence(chunk, decay):
    args = inputs(jax.random.PRNGKey(1), T=48, decay=decay)
    w = jax.random.normal(jax.random.PRNGKey(2), args[2].shape[:3] + (8,))

    def loss(f):
        def inner(*a):
            o, s = f(*a)
            return jnp.sum(o * w) + 0.1 * jnp.sum(s ** 2)
        return inner

    chunked = lambda *a: kda.chunk_kda(*a, chunk=chunk)
    g1 = jax.grad(loss(chunked), argnums=range(5))(*args)
    g2 = jax.grad(loss(naive), argnums=range(5))(*args)
    for a, b in zip(g1, g2):
        assert bool(jnp.all(jnp.isfinite(a)))
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4 * scale)


def test_chunked_continues_from_a_state():
    q, k, v, g, beta = inputs(jax.random.PRNGKey(3), T=24)
    _, s0 = naive(*(a[:, :10] for a in (q, k, v, g, beta)))
    o1, s1 = kda.chunk_kda(*(a[:, 10:] for a in (q, k, v, g, beta)),
                           chunk=8, state=s0)
    o2, s2 = naive(*(a[:, 10:] for a in (q, k, v, g, beta)), state=s0)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4,
                               atol=1e-5)


def test_unit_lower_inverse():
    A = 0.3 * jnp.tril(jax.random.normal(RNG, (3, 32, 32)), -1)
    X = kda._unit_lower_inverse(A)
    want = np.linalg.inv(np.eye(32) + np.asarray(A, np.float64))
    np.testing.assert_allclose(np.asarray(X), want, rtol=1e-4, atol=1e-5)


def test_layer_prefill_then_decode_matches_forward():
    """The layer's prefill state and one decode step give the full
    forward's last output: conv tails and the recurrent state carry over."""
    cfg = reduced(get_config("kimi-linear-48b-a3b"))
    params = Model(cfg).init(RNG)["stack"][0][0]["mixer"]
    params = dict(params, A_log=jnp.full_like(params["A_log"], 0.7))
    x = jax.random.normal(RNG, (2, 13, cfg.d_model))
    full = jax.jit(lambda p, a: kda.kda_forward(p, cfg, a))(params, x)
    _, st = jax.jit(lambda p, a: kda.kda_forward(p, cfg, a, True))(
        params, x[:, :12])
    step, _ = jax.jit(lambda p, a, s: kda.kda_decode(p, cfg, a, s))(
        params, x[:, 12:], st)
    np.testing.assert_allclose(np.asarray(step[:, 0]), np.asarray(full[:, 12]),
                               rtol=1e-4, atol=1e-5)


def test_full_plan_places_kda_and_mla_as_published():
    """Kimi-Linear's config.json: kda_layers and full_attn_layers
    (1-based), the last layer's MLA included; layer 1 has the dense FFN."""
    cfg = get_config("kimi-linear-48b-a3b")
    specs = [s for seg in cfg.stack_plan()
             for _ in range(seg.repeats) for s in seg.period]
    assert len(specs) == cfg.num_layers == 27
    mla = [i + 1 for i, s in enumerate(specs) if s.mixer == "mla"]
    kda_layers = [i + 1 for i, s in enumerate(specs) if s.mixer == "kda"]
    assert mla == [4, 8, 12, 16, 20, 24, 27]
    assert kda_layers == [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                          19, 21, 22, 23, 25, 26]
    assert [s.ffn for s in specs] == ["dense"] + ["moe"] * 26
    assert specs == [cfg.layer_spec(i) for i in range(27)]


def test_reduced_keeps_one_whole_period():
    r = reduced(get_config("kimi-linear-48b-a3b"))
    assert [s.mixer for s in r.stack_plan()[1].period] == [
        "kda", "kda", "mla", "kda"]
    assert r.num_layers == 5 and not r.mla_rope


def test_published_size():
    """About 48B parameters (the 'A3B' active share is not checked)."""
    n = get_config("kimi-linear-48b-a3b").num_params()
    assert 46e9 < n < 50e9, n


def test_periodic_plan_refuses_an_irregular_stack():
    cfg = dataclasses.replace(get_config("kimi-linear-48b-a3b"),
                              attn_extra=(6,))
    with pytest.raises(ValueError):
        cfg.stack_plan()
