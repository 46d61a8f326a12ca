"""The Kimi Linear configuration on the CPU at a small size: the program
against ``reference/kimi_linear.py`` (loss and gradients, float32), a run
of the harness that is ``correct`` and planted faults that are not, the
operation and byte counts against hand sums, and the KDA readers."""
import dataclasses
import statistics
import types

import jax
import jax.numpy as jnp
import pytest

import harness
import tiny
import tracefile as tr
import weights
from reference import kimi_linear

KIMI = {
    "reference": "kimi_linear", "vocab_key": "vocab_size",
    "hidden_size": 64, "num_attention_heads": 4, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
    "q_lora_rank": None, "num_hidden_layers": 5, "first_k_dense_replace": 1,
    "intermediate_size": 192, "moe_intermediate_size": 32,
    "router_experts": 16, "num_experts": 8, "experts_offset": 8,
    "num_experts_per_token": 2, "num_shared_experts": 1,
    "routed_scaling_factor": 2.446, "vocab_size": 256, "rms_norm_eps": 1e-5,
    "linear_attn_config": {"full_attn_layers": [4, 8, 12], "head_dim": 16,
                           "num_heads": 4, "short_conv_kernel_size": 4},
    "capacity_factor": 1.25, "aux_coef": 0.01,
    "arch": "kimi-linear-48b-a3b",
    "overrides": {"d_model": 64, "num_heads": 4, "num_kv_heads": 4,
                  "qk_nope_dim": 16, "qk_rope_dim": 8, "v_head_dim": 16,
                  "kv_lora_rank": 32, "num_layers": 5, "d_ff": 192,
                  "moe_d_ff": 32, "num_experts": 16, "experts_held": 8,
                  "experts_offset": 8, "top_k": 2, "vocab_size": 256,
                  "head_dim": 16},
}
LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-2, "delta_gap": 0.3}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2 ** 35 + 17


@pytest.fixture(autouse=True)
def four_chunks(monkeypatch):
    """Chunks of 16 tokens, so that a row of 64 crosses chunks."""
    from repro.models import kda
    monkeypatch.setattr(kda, "CHUNK", 16)


def kimi_cell(rows=3, seq=64, limits=None):
    return tiny.cell(KIMI, tiny.job("train-4x4k", rows_per_chip=rows,
                                    seq_len=seq), limits, f32=True)


def _loss(sizes):
    def f(p, tokens):
        total, count, aux = kimi_linear.nll_sum(p, sizes, tokens)
        return total / count + aux
    return f


def test_reference_matches_program_loss_and_gradients():
    from repro.models import Model
    from repro.models.sharding_ctx import clear_mesh_ctx
    clear_mesh_ctx()
    cell = kimi_cell()
    model = Model(harness.model_config(cell))
    params = jax.jit(weights.maker(harness.Params(cell).abstract))(
        weights.seed_key(2 ** 33 + 5))
    tokens = jnp.asarray(cell.traffic(11).batch(0)["tokens"])
    lp, gp = jax.jit(jax.value_and_grad(model.loss))(params,
                                                     {"tokens": tokens})
    with jax.default_matmul_precision("highest"):
        lr, gr = jax.jit(jax.value_and_grad(_loss(cell.config)))(params,
                                                                 tokens)
    assert abs(float(lp) - float(lr)) <= 2e-5 * abs(float(lr))
    gp, gr = jax.tree.leaves(gp), jax.tree.leaves(gr)
    ref = [float(jnp.linalg.norm(g)) for g in gr]
    med = statistics.median(ref)
    for a, b, n in zip(gp, gr, ref):
        err = float(jnp.linalg.norm(a - b)) / max(n, med)
        assert err < 1e-3, err


def run(cell, tamper=None):
    return harness.run(cell, SEED, 0.5, False, 0.0, device=CPU, peaks=PEAKS,
                       tamper=tamper)


def test_sound_program_is_correct_and_drops_choices():
    sessions = []
    r = run(kimi_cell(limits=LIMITS), tamper=sessions.append)
    assert r["correct"], r["checks"]
    s = sessions[0]
    assert 0 < s.dropped_tokens < s.routed_tokens


def _no_delta_chunk(S, q, k, v, g, beta, sub):
    """The chunk with the delta rule's correction left out: a gated linear
    attention, S_t = Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T."""
    from repro.models import kda
    G = jnp.cumsum(g, axis=-2)
    decay = jnp.exp(G)
    U = beta[..., None] * v
    o = (q * decay) @ S + kda._pair_products(q, k, G, sub)[1] @ U
    k_end = k * jnp.exp(G[..., -1:, :] - G)
    return decay[..., -1, :, None] * S + jnp.swapaxes(k_end, -1, -2) @ U, o


def _every_expert(orig):
    """A layer that computes the choices of every expert, the absent
    experts' with this chip's weights."""
    from repro.models.layers import mlp

    def ffn(params, cfg, x, **kw):
        shared = mlp(params["shared"], x.reshape(-1, x.shape[-1]),
                     cfg.activation).reshape(x.shape)
        out, aux = shared, None
        for off in range(0, cfg.num_experts, cfg.experts_held):
            o, a = orig(params, dataclasses.replace(cfg, experts_offset=off),
                        x, **kw)
            out = out + (o - shared)
            aux = a if off == cfg.experts_offset else aux
        return out, aux
    return ffn


@pytest.mark.parametrize("fault", ["no_delta", "every_expert"])
def test_fault_is_not_correct(fault, monkeypatch):
    from repro.models import kda, moe
    if fault == "no_delta":
        monkeypatch.setattr(kda, "_chunk", _no_delta_chunk)
    else:
        monkeypatch.setattr(moe, "moe_ffn", _every_expert(moe.moe_ffn))
    r = run(kimi_cell(limits=LIMITS))
    assert not r["correct"], r["checks"]


def test_forward_flops_and_kda_bytes_match_hand_sums():
    s, seq = KIMI, 64
    d, hk, r = 64, 4 * 16, 16
    kda_p = 4 * d * hk + 2 * (d * r + r * hk) + d * 4           # 17664
    mla_p = d * 4 * 24 + d * (32 + 8) + 32 * 4 * 32 + 4 * 16 * d
    dense = 3 * d * 192
    moe = d * 16 + 3 * d * 32 * 1 + 3 * d * 32 * 2 * 8 / 16
    head = 256 * d
    mixing = 4 * 6 * 4 * 16 * 16 + 2 * 4 * (16 + 8 + 16) * (seq + 1) / 2
    want = 2 * (4 * kda_p + mla_p + dense + 4 * moe + head) + mixing
    assert kimi_linear.kda_params(s) == kda_p
    assert kimi_linear.forward_flops(s, seq) == pytest.approx(want, rel=1e-12)
    tokens = 3 * 64
    assert kimi_linear.kda_step_flops(s, tokens) == pytest.approx(
        3 * 4 * (2 * kda_p + 6 * 4 * 16 * 16) * tokens, rel=1e-12)
    assert kimi_linear.kda_step_bytes(s, tokens) == pytest.approx(
        4 * 2 * (3 * kda_p + 5 * tokens * d), rel=1e-12)


def test_published_counts():
    import json
    import os
    with open(os.path.join(harness.HERE, "configs",
                           "kimi-linear-48b-a3b-5l.json")) as f:
        s = json.load(f)
    assert kimi_linear.layer_kinds(s) == ["kda"] * 3 + ["mla", "kda"]
    assert kimi_linear.kda_params(s) == pytest.approx(39.46e6, rel=1e-3)
    assert 3 * kimi_linear.forward_flops(s, 4096) == pytest.approx(
        2.18e9, rel=0.01)


def test_program_holds_the_stated_share():
    """The program's tree at the published widths: 8 experts held of a
    256-wide router, about 602M parameters."""
    import json
    import os
    with open(os.path.join(harness.HERE, "configs",
                           "kimi-linear-48b-a3b-5l.json")) as f:
        conf = json.load(f)
    cell = tiny.cell(conf, tiny.job("train-4x4k"))
    abstract = harness.Params(cell).abstract
    ffn = abstract["stack"][1][0]["ffn"]
    assert ffn["router"].shape == (2304, 256)
    assert ffn["wi_gate"].shape == (8, 2304, 1024)
    n = sum(x.size for x in jax.tree.leaves(abstract))
    assert n == pytest.approx(602e6, rel=0.01)


def test_kda_ms_reads_the_kda_scope():
    """The reader sums the self time of the ops whose scope names hold
    ``kda`` (here a fusion under ``attn/kda/chunk``), and nothing where
    no op has one."""
    import scopes
    from metrics import kda_ms, kda_roofline
    hlo = "\n".join([
        "ENTRY %main.1 (p: f32[8]) -> f32[8] {",
        '  %fusion.2 = f32[8]{0} add(%p, %p), metadata={op_name='
        '"jit(step)/forward/attn/kda/chunk/add"}',
        '  %fusion.3 = f32[8]{0} add(%p, %p), metadata={op_name='
        '"jit(step)/transpose(jvp(forward))/attn/mla/add"}',
        '  ROOT %fusion.4 = f32[8]{0} add(%p, %p), metadata={op_name='
        '"jit(step)/transpose(jvp(forward))/attn/kda/proj/dot"}',
        "}"])
    program = types.SimpleNamespace(as_text=lambda: hlo)
    trace = tr.Trace({"/device:TPU:0": [("fusion.2", 0, 3_000_000),
                                        ("fusion.3", 3_000_000, 4_000_000),
                                        ("fusion.4", 4_000_000, 6_000_000)]},
                     [("bench.window", 0, 10_000_000)])
    cell = kimi_cell(rows=1, seq=64)
    ctx = harness.Context(trace=trace, cell=cell,
                          session=types.SimpleNamespace(programs={"b": program}),
                          peaks=PEAKS, flops_per_token=0.0, steps=2)
    assert scopes.program_scopes(ctx.session) is not None
    assert kda_ms.read(ctx) == pytest.approx(2.5)
    tokens = 64
    t = max(kimi_linear.kda_step_flops(KIMI, tokens) / 1e12,
            kimi_linear.kda_step_bytes(KIMI, tokens) / 1e11)
    assert kda_roofline.read(ctx) == pytest.approx(100 * t / 2.5e-3)
    none = harness.Context(trace=tr.Trace(dict(trace.devices), trace.spans),
                           cell=cell, session=types.SimpleNamespace(
                               programs={"b": types.SimpleNamespace(
                                   as_text=lambda: hlo.replace("kda", "mla"))}),
                           peaks=PEAKS, flops_per_token=0.0, steps=2)
    assert kda_ms.read(none) is None and kda_roofline.read(none) is None
