"""Pallas kernels vs ref.py oracles: shape x dtype sweeps in interpret mode
(deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = jax.random.PRNGKey(7)


@pytest.mark.parametrize("B,T,H,KV,hd", [
    (1, 128, 2, 2, 32), (2, 256, 4, 2, 64), (1, 128, 8, 1, 32),
    (2, 128, 4, 4, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernel_shapes(B, T, H, KV, hd, dtype):
    q = jax.random.normal(RNG, (B, T, H, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(RNG, 1), (B, T, KV, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(RNG, 2), (B, T, KV, hd), dtype)
    out = ops.flash_attention(q, k, v, q_blk=64, kv_blk=64)
    r = ref.flash_attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                                v.astype(jnp.float32))
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(r),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("kwargs", [
    dict(window=64), dict(softcap=30.0), dict(window=64, softcap=20.0),
    dict(causal=False),
])
def test_flash_kernel_variants(kwargs):
    q = jax.random.normal(RNG, (1, 256, 4, 32))
    k = jax.random.normal(jax.random.fold_in(RNG, 1), (1, 256, 2, 32))
    v = jax.random.normal(jax.random.fold_in(RNG, 2), (1, 256, 2, 32))
    out = ops.flash_attention(q, k, v, q_blk=64, kv_blk=64, **kwargs)
    r = ref.flash_attention_ref(q, k, v, **kwargs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_tiles", [1, 3, 8])
@pytest.mark.parametrize("decay", [1.0, 0.9])
def test_quantize_ef_kernel(n_tiles, decay):
    n = n_tiles * 1024
    g = jax.random.normal(RNG, (n,)) * 2.5
    e = jax.random.normal(jax.random.fold_in(RNG, 1), (n,)) * 0.3
    q, e_new, sc = ops.quantize_ef(g, e, decay=decay, tile=1024)
    qr, er, scr = ref.quantize_ef_ref(g, e, decay=decay, tile=1024)
    assert q.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    # atol covers fused-vs-ref rounding differences across jaxlib versions
    # (observed up to ~1.3e-6 on the CPU interpreter backend)
    np.testing.assert_allclose(np.asarray(e_new), np.asarray(er), atol=3e-6)
    np.testing.assert_allclose(np.asarray(sc), np.asarray(scr), atol=0)


def test_quantize_ef_reconstruction_bound():
    """|corrected - dequant(q)| <= scale/254 per element (round-to-nearest)."""
    from repro.kernels.ops import dequantize
    n = 4096
    g = jax.random.normal(RNG, (n,)) * 5
    e = jnp.zeros((n,))
    q, e_new, sc = ops.quantize_ef(g, e, tile=1024)
    recon = dequantize(q, sc, tile=1024)
    bound = jnp.repeat(sc, 1024) / 127.0 * 0.5 + 1e-6
    assert bool(jnp.all(jnp.abs(g - recon) <= bound))
    np.testing.assert_allclose(np.asarray(g - recon), np.asarray(e_new),
                               rtol=1e-3, atol=1e-5)


# ---------------------------------------------------------------------------
# Backend dispatch (DESIGN.md §11) — the regression tests for the historical
# unconditional-interpret default
# ---------------------------------------------------------------------------

def test_dispatch_defaults_follow_backend(monkeypatch):
    """No caller may hardcode interpret mode: ``None`` resolves to the
    compiled kernel on TPU and the xla/interpreter lowering elsewhere, and
    ``REPRO_KERNELS_IMPL`` overrides the default (explicit args win)."""
    from repro.kernels import dispatch

    monkeypatch.delenv(dispatch.IMPL_ENV, raising=False)
    expect = "pallas" if dispatch.on_tpu() else "xla"
    assert dispatch.resolve_impl(None) == expect
    assert dispatch.resolve_interpret(None) == (not dispatch.on_tpu())
    assert dispatch.resolve_interpret(True) is True
    assert dispatch.resolve_interpret(False) is False

    monkeypatch.setenv(dispatch.IMPL_ENV, "interpret")
    assert dispatch.resolve_impl(None) == "interpret"
    monkeypatch.setenv(dispatch.IMPL_ENV, "xla")
    assert dispatch.resolve_impl(None) == "xla"
    # an explicit impl beats the env override
    assert dispatch.resolve_impl("interpret") == "interpret"
    monkeypatch.setenv(dispatch.IMPL_ENV, "bogus")
    with pytest.raises(ValueError, match="bogus"):
        dispatch.resolve_impl(None)


def test_hot_path_is_not_interpreter_off_tpu(monkeypatch):
    """ops.py's default dispatch off-TPU must be the vectorized xla
    lowering, never the Pallas interpreter (the perf bug this PR fixes):
    the jitted wrapper receives impl='xla'."""
    from repro.kernels import dispatch
    monkeypatch.delenv(dispatch.IMPL_ENV, raising=False)
    if dispatch.on_tpu():
        pytest.skip("off-TPU dispatch check")
    seen = {}
    orig = ops._quantize_ef

    def spy(g, e, decay, tile, impl):
        seen["impl"] = impl
        return orig(g, e, decay, tile, impl)

    monkeypatch.setattr(ops, "_quantize_ef", spy)
    g = jax.random.normal(RNG, (1024,))
    ops.quantize_ef(g, jnp.zeros_like(g), tile=1024)
    assert seen["impl"] == "xla"


# interpret (the Pallas kernel body under the interpreter) and xla (the
# ref.py lowering) must agree BITWISE under jit — that equivalence is what
# lets the off-TPU hot path skip the interpreter without changing any
# payload or residual.  Ragged lengths exercise the pad-and-mask contract;
# 301 tiles + 17 also leaves a partial last block of rows in every kernel.
@pytest.mark.parametrize("n", [1024, 1000, 2065, 4096, 301 * 1024 + 17])
def test_interpret_matches_xla_bitwise(n):
    g = jax.random.normal(RNG, (n,)) * 2.0
    e = jax.random.normal(jax.random.fold_in(RNG, 1), (n,)) * 0.3

    for a, b in zip(ops.quantize_ef(g, e, tile=1024, impl="interpret"),
                    ops.quantize_ef(g, e, tile=1024, impl="xla")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(ops.topk_ef(g, e, ratio=0.25, tile=1024,
                                impl="interpret"),
                    ops.topk_ef(g, e, ratio=0.25, tile=1024, impl="xla")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    q, sc = ops.quantize_tiles(g, tile=1024, impl="xla")
    for a, b in zip(ops.quantize_tiles(g, tile=1024, impl="interpret"),
                    (q, sc)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    qg, sg = jnp.stack([q] * 4), jnp.stack([sc] * 4)
    np.testing.assert_array_equal(
        np.asarray(ops.dequant_accum(qg, sg, tile=1024, impl="interpret")),
        np.asarray(ops.dequant_accum(qg, sg, tile=1024, impl="xla")))


@pytest.mark.parametrize("n", [1000, 2065])
def test_ragged_pad_and_mask_contract(n):
    """Non-tile-aligned lengths: zero-pad to the boundary, compute, slice
    back — the partial tile's scale and residual must match computing on
    the padded array directly (pads cannot change max|·| or be kept by a
    positive threshold), and the EF identity y + e_new == g + e holds on
    the ragged buffer."""
    tile = 1024
    m = -(-n // tile) * tile
    g = jax.random.normal(RNG, (n,)) * 2.0
    e = jax.random.normal(jax.random.fold_in(RNG, 1), (n,)) * 0.3
    gp = jnp.pad(g, (0, m - n))
    ep = jnp.pad(e, (0, m - n))

    q, e_new, sc = ops.quantize_ef(g, e, tile=tile)
    qp, ep_new, scp = ops.quantize_ef(gp, ep, tile=tile)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qp)[:n])
    np.testing.assert_array_equal(np.asarray(e_new), np.asarray(ep_new)[:n])
    np.testing.assert_array_equal(np.asarray(sc), np.asarray(scp))
    # the pad region of the padded run stays exactly zero
    assert not np.asarray(qp)[n:].any() and not np.asarray(ep_new)[n:].any()

    y, e2 = ops.topk_ef(g, e, ratio=0.25, tile=tile)
    yp, ep2 = ops.topk_ef(gp, ep, ratio=0.25, tile=tile)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yp)[:n])
    np.testing.assert_array_equal(np.asarray(e2), np.asarray(ep2)[:n])
    np.testing.assert_allclose(np.asarray(y) + np.asarray(e2),
                               np.asarray(g + e), atol=1e-6)

    q2, sc2 = ops.quantize_tiles(g, tile=tile)
    d = ops.dequant_accum(jnp.stack([q2] * 3), jnp.stack([sc2] * 3),
                          tile=tile)
    assert d.shape == (n,)
    np.testing.assert_allclose(
        np.asarray(d), 3 * np.asarray(ref.dequantize_ref(q2, sc2, tile=tile)),
        rtol=1e-6, atol=1e-6)


def test_dequant_accum_matches_per_payload_loop():
    """The fused decode (one read per payload, one dense write) equals the
    decomposed per-rank dequantize+add loop up to summation order."""
    n, w, tile = 4096, 8, 1024
    qs, scs = [], []
    for i in range(w):
        x = jax.random.normal(jax.random.fold_in(RNG, i), (n,)) * (1 + i)
        q, sc = ops.quantize_tiles(x, tile=tile)
        qs.append(q)
        scs.append(sc)
    got = ops.dequant_accum(jnp.stack(qs), jnp.stack(scs), tile=tile)
    want = sum(ref.dequantize_ref(q, sc, tile=tile)
               for q, sc in zip(qs, scs))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("ratio", [0.01, 0.05, 0.25])
def test_topk_mask_kernel(ratio):
    n = 8 * 1024
    x = jax.random.normal(RNG, (n,))
    got = ops.topk_mask(x, ratio=ratio, tile=1024)
    want = ref.topk_mask_ref(x, ratio=ratio, tile=1024)
    k = max(1, int(1024 * ratio))
    nnz = int((got != 0).sum())
    # per-tile counts within bisection tolerance of the exact oracle
    assert abs(nnz - int((want != 0).sum())) <= 8 * 2
    # kept values are a subset relationship: every kept kernel value matches x
    kept = np.asarray(got != 0)
    np.testing.assert_array_equal(np.asarray(got)[kept], np.asarray(x)[kept])
    # magnitudes: min kept >= max dropped within each tile (up to bisection eps)
    xb = np.asarray(x).reshape(-1, 1024)
    gb = np.asarray(got).reshape(-1, 1024)
    for xt, gt in zip(xb, gb):
        kept_t = gt != 0
        if kept_t.any() and (~kept_t).any():
            assert np.abs(xt[kept_t]).min() >= np.abs(xt[~kept_t]).max() - 1e-4
