"""The plain references against the program's ``Model.loss`` and its
gradients, at a small size on the CPU, both in float32."""
import statistics

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import tiny
import weights
from reference import deepseek_v2


def _loss(module, sizes):
    def f(p, tokens):
        total, count, aux = module.nll_sum(p, sizes, tokens)
        return total / count + aux
    return f


def test_reference_matches_program_loss_and_gradients():
    from repro.models import Model
    from repro.models.sharding_ctx import clear_mesh_ctx
    clear_mesh_ctx()
    cell = tiny.cell(tiny.DEEPSEEK, tiny.job("train-4k", rows_per_chip=3,
                                             seq_len=64), f32=True)
    model = Model(harness.model_config(cell))
    params = jax.jit(weights.maker(harness.Params(cell).abstract))(
        weights.seed_key(2 ** 33 + 5))
    tokens = jnp.asarray(cell.traffic(11).batch(0)["tokens"])
    lp, gp = jax.value_and_grad(model.loss)(params, {"tokens": tokens})
    with jax.default_matmul_precision("highest"):
        lr, gr = jax.value_and_grad(_loss(deepseek_v2, cell.config))(params, tokens)
    assert abs(float(lp) - float(lr)) <= 2e-5 * abs(float(lr))
    gp, gr = jax.tree.leaves(gp), jax.tree.leaves(gr)
    ref = [float(jnp.linalg.norm(g)) for g in gr]
    med = statistics.median(ref)
    for a, b, n in zip(gp, gr, ref):
        err = float(jnp.linalg.norm(a - b)) / max(n, med)
        assert err < 1e-3, err


def test_control_rounds_operands_and_cotangent():
    from reference import common
    x = jnp.linspace(-3.0, 3.0, 64).reshape(8, 8)
    w = jnp.eye(8) * 1.37
    exact = common.mm(x, w)
    with common.lower_precision(jnp.bfloat16):
        low = common.mm(x, w)
        g = jax.grad(lambda a: common.mm(a, w).sum())(x)
    assert float(jnp.max(jnp.abs(low - exact))) > 1e-4
    # d/dx sum(round(x) @ round(w)) with a cotangent of ones (exact in
    # bfloat16) is the row sum of round(w)
    want = float(jnp.asarray(1.37, jnp.bfloat16))
    np.testing.assert_allclose(np.asarray(g), np.full((8, 8), want),
                               rtol=1e-6)


def test_int8_control_keeps_the_largest_value_and_flushes_small_ones():
    from reference import common
    x = jnp.asarray([[1000.0, 2.0, -0.001]])
    w = jnp.eye(3)
    with common.lower_precision(jnp.int8):
        y = common.mm(x, w)
    np.testing.assert_allclose(np.asarray(y), [[1000.0, 0.0, 0.0]],
                               atol=1e-3)


def _whole_batch_moe(p, s, x):
    """The MoE layer as the reference computed it before it took a shard
    count: routing, capacity and the balance loss over the whole batch."""
    from reference import common as C
    B, T, d = x.shape
    E, k = s["n_routed_experts"], s["num_experts_per_tok"]
    n = B * T
    xf = x.reshape(n, d)
    probs = jax.nn.softmax(C.mm(xf, p["router"]), axis=-1)
    w, ex = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    aux = E * jnp.sum(jax.nn.one_hot(ex[:, 0], E).mean(0) * probs.mean(0))
    cap = int(max(1, n * k / E * s["capacity_factor"]))
    hot = jax.nn.one_hot(ex.reshape(-1), E, dtype=jnp.int32)
    slot = jnp.sum((jnp.cumsum(hot, axis=0) - 1) * hot, axis=-1)
    keep = (slot < cap).reshape(n, k)
    comb = jnp.einsum("nk,nke->ne", w * keep,
                      jax.nn.one_hot(ex, E, dtype=jnp.float32))

    @jax.checkpoint
    def expert(acc, args):
        wg, wu, wo, c = args
        y = C.mm(jax.nn.silu(C.mm(xf, wg)) * C.mm(xf, wu), wo)
        return acc + c[:, None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(xf),
                          (p["wi_gate"], p["wi_up"], p["wo"], comb.T))
    if s["n_shared_experts"]:
        out = out + deepseek_v2._swiglu(p["shared"], xf)
    return out.reshape(B, T, d), aux


def _tiny_params_and_tokens(rows):
    cell = tiny.cell(tiny.DEEPSEEK, tiny.job("train-4k", rows_per_chip=rows,
                                             seq_len=64), f32=True)
    params = jax.jit(weights.maker(harness.Params(cell).abstract))(
        weights.seed_key(2 ** 33 + 9))
    return cell.config, params, jnp.asarray(cell.traffic(13).batch(0)["tokens"])


def test_one_shard_is_the_whole_batch_bit_for_bit(monkeypatch):
    """With one shard the reference computes what it computed when it
    routed the whole batch: loss and gradients equal to the last bit."""
    sizes, params, tokens = _tiny_params_and_tokens(4)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(_loss(deepseek_v2, sizes)))(
            params, tokens)
        monkeypatch.setattr(deepseek_v2, "_moe",
                            lambda p, s, x, shards: _whole_batch_moe(p, s, x))
        want = jax.jit(jax.value_and_grad(_loss(deepseek_v2, sizes)))(
            params, tokens)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_each_shard_routes_alone():
    """Four shards of one row each: the summed NLL is the sum of each row's
    alone, and the balance loss the mean of each row's, at a size where the
    whole batch's routing would drop other choices."""
    sizes, params, tokens = _tiny_params_and_tokens(4)
    with jax.default_matmul_precision("highest"):
        total, count, aux = jax.jit(
            lambda p, t: deepseek_v2.nll_sum(p, sizes, t, shards=4))(
                params, tokens)
        rows = [jax.jit(lambda p, t: deepseek_v2.nll_sum(p, sizes, t))(
            params, tokens[i:i + 1]) for i in range(4)]
        whole = jax.jit(lambda p, t: deepseek_v2.nll_sum(p, sizes, t))(
            params, tokens)
    np.testing.assert_allclose(float(total), sum(float(r[0]) for r in rows),
                               rtol=1e-5)
    assert float(count) == sum(float(r[1]) for r in rows)
    np.testing.assert_allclose(float(aux), np.mean([float(r[2]) for r in rows]),
                               rtol=1e-5)
    assert abs(float(whole[0]) - float(total)) > 1e-4 * abs(float(total))
