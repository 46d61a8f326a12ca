"""chunked_scan equivalence (hypothesis over lengths/chunks), sharding-ctx
constraint semantics, TIC/TAC schedules, and asymmetric push/pull."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hyp_compat import given, settings, st

from repro.models.scan_utils import chunked_scan

RNG = jax.random.PRNGKey(21)


@given(st.integers(1, 64), st.integers(1, 32), st.booleans())
@settings(max_examples=25, deadline=None)
def test_chunked_scan_equals_scan(T, chunk, ckpt):
    xs = jnp.sin(jnp.arange(T * 3, dtype=jnp.float32)).reshape(T, 3)

    def step(c, x):
        c = jnp.tanh(c + x.sum())
        return c, c * x

    c_ref, ys_ref = jax.lax.scan(step, jnp.zeros(()), xs)
    c, ys = chunked_scan(step, jnp.zeros(()), xs, chunk=chunk,
                         checkpoint_step=ckpt)
    np.testing.assert_allclose(np.asarray(c), np.asarray(c_ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(ys_ref), rtol=1e-6)


def test_chunked_scan_gradient_matches():
    T = 32
    xs = jax.random.normal(RNG, (T, 4))

    def run(fn):
        def loss(xs):
            _, ys = fn(lambda c, x: (0.9 * c + x, jnp.tanh(c)),
                       jnp.zeros((4,)), xs)
            return jnp.sum(ys ** 2)
        return jax.grad(loss)(xs)

    g_ref = run(jax.lax.scan)
    g = run(lambda s, i, x: chunked_scan(s, i, x, chunk=8))
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-5,
                               atol=1e-6)


def test_constrain_noop_without_mesh():
    from repro.models.sharding_ctx import constrain, constrain_hard
    x = jnp.ones((4, 8))
    assert constrain(x, ("b", "m")) is x
    assert constrain_hard(x, ("b", None)) is x


def test_constrain_divisibility_guard():
    """On a real mesh, non-divisible dims must never be pinned to an axis."""
    import subprocess, sys, os
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.models.sharding_ctx import constrain, constrain_hard, mesh_ctx
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,)*2)
with mesh_ctx(mesh, ("data",)):
    @jax.jit
    def f(x):
        # dim0=6 not divisible by data=4 -> must not shard; dim1=8 by model=2 ok
        return constrain(x, ("b", "m")) * 2
    out = f(jnp.ones((6, 8)))
    assert out.shape == (6, 8)
    @jax.jit
    def g(x):
        return constrain_hard(x, ("b", "m")) + 1
    assert g(jnp.ones((8, 6))).shape == (8, 6)
print("OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0 and "OK" in res.stdout, res.stderr


def test_tic_tac_schedules():
    from repro.core.schedule import (LayerProfile, iteration_time_fifo,
                                     iteration_time_tic, iteration_time_tac)
    layers = [LayerProfile(1e-3, 5e6)] * 12
    a, b = 5e-6, 1 / 10e9
    fifo = iteration_time_fifo(layers, a, b)
    tic = iteration_time_tic(layers, a, b)
    tac = iteration_time_tac(layers, a, b)
    tb = sum(l.t_backward_s for l in layers)
    for t in (tic, tac):
        assert tb - 1e-12 <= t <= fifo + 1e-9


def test_asymmetric_push_pull():
    from repro.core.local_sgd import AsymmetricPushPullConfig
    cfg = AsymmetricPushPullConfig(n_push=2, n_fetch=3)
    r = cfg.rounds(12)
    assert r == {"push": 6, "fetch": 4}
    assert cfg.should_push(1) and not cfg.should_push(0)
    assert cfg.should_fetch(2) and not cfg.should_fetch(0)


def test_per_leaf_ef_equals_bucketed_for_single_leaf():
    """With one leaf, per-leaf (bucket_bytes=0) and bucketed sync agree up to
    the flatten (same compressor semantics on the same values)."""
    from repro.core import GradientSynchronizer, SyncConfig
    g = {"w": jax.random.normal(RNG, (64,))}
    outs = []
    for bb in (0, 1 << 30):
        sync = GradientSynchronizer(
            SyncConfig(compressor="int8", algo="ring", bucket_bytes=bb), ())
        st = sync.init_state(g)
        out, st2 = sync(g, st, jax.random.PRNGKey(0))
        outs.append(np.asarray(jax.tree.leaves(out)[0]).reshape(-1))
        assert int(st2["step"]) == 1
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-6)
