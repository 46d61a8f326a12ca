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
