"""Per-architecture smoke tests (deliverable f): every assigned arch's
reduced variant runs one forward/train step and one decode step on CPU with
finite outputs and correct shapes, and prefill+decode is consistent with the
full forward pass (cache correctness, including sliding-window ring buffers
and recurrent states)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_config, reduced
from repro.models import Model
from repro.models.transformer import materialize_cache

RNG = jax.random.PRNGKey(0)


def make(name):
    cfg = reduced(get_config(name))
    model = Model(cfg)
    params = model.init(RNG)
    return cfg, model, params


def batch_for(cfg, B=2, T=32):
    b = {"tokens": jax.random.randint(RNG, (B, T), 0, cfg.vocab_size)}
    if cfg.is_encoder_decoder:
        b["src"] = jax.random.normal(RNG, (B, T, cfg.d_model)) * 0.1
    return b


# the heavyweight reduced archs (~20-30 s each on CPU) ride in the slow
# CI tier; the rest stay in the default tier-1 selection
_HEAVY = {"xlstm-125m", "deepseek-v2-lite-16b", "jamba-v0.1-52b",
          "gemma2-9b"}
# kimi-linear's train step takes ~60 s (its chunked KDA backward compiles
# slowly); its prefill/decode check stays in tier 1
_HEAVY_TRAIN = _HEAVY | {"kimi-linear-48b-a3b"}


@pytest.mark.parametrize(
    "name", [pytest.param(a, marks=pytest.mark.slow) if a in _HEAVY_TRAIN
             else a for a in ALL_ARCHS])
def test_train_step_smoke(name):
    cfg, model, params = make(name)
    batch = batch_for(cfg)
    loss, grads = jax.value_and_grad(model.loss)(params, batch)
    assert jnp.isfinite(loss), name
    # loss at init ~ ln(vocab)
    assert abs(float(loss) - np.log(cfg.vocab_size)) < 1.5, float(loss)
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                         for g in jax.tree.leaves(grads)))
    assert jnp.isfinite(gnorm) and float(gnorm) > 0


@pytest.mark.parametrize("name", ALL_ARCHS)
def test_decode_step_smoke(name):
    cfg, model, params = make(name)
    B, L = 2, 16
    cache = materialize_cache(model.init_cache(B, L, src_len=L))
    if cfg.is_encoder_decoder:
        b = batch_for(cfg, B, 8)
        _, cache = model.prefill(params, b, max_len=L)
        pos = 8
    else:
        pos = 0
    tok = jax.random.randint(RNG, (B, 1), 0, cfg.vocab_size)
    logits, new_cache = model.decode_step(params, tok, cache,
                                          jnp.asarray(pos, jnp.int32))
    assert logits.shape == (B, 1, cfg.padded_vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))
    # cache structure preserved
    assert jax.tree.structure(new_cache) == jax.tree.structure(cache)


@pytest.mark.parametrize(
    "name", [pytest.param(a, marks=pytest.mark.slow) if a in _HEAVY
             else a for a in ("gemma-2b", "deepseek-v2-lite-16b",
                              "xlstm-125m", "jamba-v0.1-52b",
                              "seamless-m4t-large-v2", "gemma3-4b",
                              "kimi-linear-48b-a3b")])
def test_prefill_decode_matches_full_forward(name):
    """logits(prefill P tokens, then decode one) == logits(prefill P+1).
    MoE capacity is raised so no tokens drop (drops differ between the two
    tokenizations and are not a cache bug)."""
    import dataclasses
    cfg, model, params = make(name)
    if cfg.num_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
        model = Model(cfg)
    B, P = 2, 12
    b = batch_for(cfg, B, P + 1)
    full_logits, _ = model.prefill(params, b, max_len=P + 4)

    b_pre = {k: (v[:, :P] if k == "tokens" else v) for k, v in b.items()}
    _, cache = model.prefill(params, b_pre, max_len=P + 4)
    step_logits, _ = model.decode_step(params, b["tokens"][:, P:P + 1], cache,
                                       jnp.asarray(P, jnp.int32))
    np.testing.assert_allclose(np.asarray(step_logits), np.asarray(full_logits),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.slow
def test_sliding_window_ring_cache():
    """Decode far past the window: ring buffer must evict correctly."""
    cfg = reduced(get_config("gemma3-4b"))   # window 32 after reduction
    model = Model(cfg)
    params = model.init(RNG)
    T = cfg.window_size + 16                  # exceed the window
    tokens = jax.random.randint(RNG, (1, T + 1), 0, cfg.vocab_size)
    full_logits, _ = model.prefill(params, {"tokens": tokens}, max_len=T + 4)
    _, cache = model.prefill(params, {"tokens": tokens[:, :T]}, max_len=T + 4)
    step_logits, _ = model.decode_step(params, tokens[:, T:T + 1], cache,
                                       jnp.asarray(T, jnp.int32))
    np.testing.assert_allclose(np.asarray(step_logits), np.asarray(full_logits),
                               rtol=2e-3, atol=2e-3)


def test_mla_absorb_equivalence():
    """Absorbed MLA decode (the §Perf optimization) == naive MLA decode."""
    cfg, model, params = make("deepseek-v2-lite-16b")
    B, P = 2, 8
    b = batch_for(cfg, B, P)
    _, cache = model.prefill(params, b, max_len=P + 4)
    tok = b["tokens"][:, -1:]
    l1, _ = model.decode_step(params, tok, cache, jnp.asarray(P, jnp.int32),
                              mla_absorb=False)
    l2, _ = model.decode_step(params, tok, cache, jnp.asarray(P, jnp.int32),
                              mla_absorb=True)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=2e-3, atol=2e-3)


def test_moe_routing_mass_conservation():
    """Every kept token's expert outputs are weighted by normalized router
    weights; with identical expert weights MoE == dense MLP of same size."""
    import dataclasses
    from repro.models import moe as moe_mod
    # capacity_factor high enough that nothing is dropped (drop-free check)
    cfg = dataclasses.replace(reduced(get_config("qwen3-moe-30b-a3b")),
                              capacity_factor=8.0)
    model = Model(cfg)
    params = model.init(RNG)
    # find a MoE ffn params leaf and make all experts identical
    seg = params["stack"][0][0]["ffn"]
    for k in ("wi_gate", "wi_up", "wo"):
        w0 = seg[k][(0,) * 1]  # stacked (repeats, E, ...)
        seg[k] = jnp.broadcast_to(seg[k][:, :1], seg[k].shape)
    x = jax.random.normal(RNG, (2, 16, cfg.d_model)) * 0.3
    out, aux = moe_mod.moe_ffn(jax.tree.map(lambda p: p[0], seg), cfg, x)
    # identical experts + normalized weights -> same as single expert MLP
    from repro.models.layers import mlp
    dense = mlp({"wi_gate": seg["wi_gate"][0, 0], "wi_up": seg["wi_up"][0, 0],
                 "wo": seg["wo"][0, 0]}, x, cfg.activation)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=2e-2, atol=2e-2)
    assert jnp.isfinite(aux["balance"])
    assert float(aux["dropped"]) == 0.0          # capacity for every choice
