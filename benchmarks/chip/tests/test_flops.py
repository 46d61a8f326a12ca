"""flops.py against two independent counts: the program's parameter tree
(shapes only), and the matmul operations XLA compiles for the step."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import tiny
from flops import train_flops_per_token
from weights import ZERO

CONFIGS = os.path.join(harness.HERE, "configs")


def _load(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def _from_tree(cell, seq):
    """2 x (matrix weights a token passes through, routed experts at
    top_k / E, no embedding lookup, no stacked norm scales or biases, no
    vocabulary padding) + the attention term."""
    s = cell.config
    abstract = harness.Params(cell).abstract
    total = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(abstract)[0]:
        keys = [p.key for p in path if hasattr(p, "key")]
        if len(leaf.shape) < 2 or keys[0] == "embed" or keys[-1] in ZERO:
            continue
        n = float(np.prod(leaf.shape))
        if keys[0] == "lm_head":      # the program pads rows to 256s
            n = float(s[s["vocab_key"]] * leaf.shape[1])
        if cell.family == "deepseek_v2" and "ffn" in keys and \
                keys[-1] in ("wi_gate", "wi_up", "wo") and "shared" not in keys \
                and leaf.shape[-3 if len(leaf.shape) == 3 else 0] == \
                s.get("n_routed_experts"):
            n *= s["num_experts_per_tok"] / s["n_routed_experts"]
        total += n
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    mixing = (s["num_hidden_layers"] * 2 * s["num_attention_heads"]
              * (qk + s["v_head_dim"]) * (seq + 1) / 2)
    return 3 * (2 * total + mixing)


def _published(name):
    c = _load(name)
    return tiny.cell(c, tiny.job("train-4k"))


def test_published_sizes_match_the_parameter_tree():
    name, seq, about = "deepseek-v2-lite-2l", 4096, 1.42e9
    cell = _published(name)
    got = train_flops_per_token(cell.config, cell.family, seq)
    assert got == pytest.approx(_from_tree(cell, seq), rel=1e-9)
    assert got == pytest.approx(about, rel=0.02)


def test_no_more_than_the_compiled_step():
    """The compiled step's matmul operations, with loop bodies counted
    once per trip, are at least what flops.py says the step needs."""
    import hlo_flops
    from repro.launch.steps import make_train_step
    from repro.models import Model
    from repro.models.sharding_ctx import clear_mesh_ctx
    from repro.optim import make_optimizer
    clear_mesh_ctx()
    rows, seq = 2, 64
    cell = tiny.cell(tiny.DEEPSEEK, tiny.job("train-4k", rows_per_chip=rows,
                                      seq_len=seq), f32=True)
    model = Model(harness.model_config(cell))
    opt = make_optimizer("adam", lr=1e-3)
    params = harness.Params(cell).abstract
    step = jax.jit(make_train_step(model, opt)).lower(
        params, jax.eval_shape(opt.init, params),
        {"tokens": jax.ShapeDtypeStruct((rows, seq), jnp.int32)},
        jax.ShapeDtypeStruct((), jnp.int32)).compile()
    compiled = hlo_flops.matmul_flops(step.as_text())
    need = train_flops_per_token(cell.config, cell.family, seq) * rows * seq
    assert 0 < need <= compiled


# A TPU program in the shape the chip's compiler prints it: a batched
# matmul written as a convolution whose window spans the batch (one real
# tap per window), inside a loop of 3 trips whose bound carries a layout.
TPU_HLO = """\
%fused_computation.1 (param_0: bf16[64,960,2048], param_1: bf16[64,1408,960]) -> bf16[64,2048,1408] {
  %param_0 = bf16[64,960,2048]{2,1,0:T(8,128)(2,1)} parameter(0)
  %param_1 = bf16[64,1408,960]{1,2,0:T(8,128)(2,1)} parameter(1)
  ROOT %convolution.2 = bf16[64,2048,1408]{2,1,0:T(8,128)(2,1)} convolution(%param_0, %param_1), window={size=64 stride=63 lhs_dilate=64}, dim_labels=0fb_0oi->0bf
}

%body.3 (arg: (s32[], bf16[64,960,2048], bf16[64,1408,960])) -> (s32[], bf16[64,960,2048], bf16[64,1408,960]) {
  %arg = (s32[]{:T(128)}, bf16[64,960,2048]{2,1,0}, bf16[64,1408,960]{1,2,0}) parameter(0)
  %a = bf16[64,960,2048]{2,1,0} get-tuple-element(%arg), index=1
  %b = bf16[64,1408,960]{1,2,0} get-tuple-element(%arg), index=2
  %fusion.4 = bf16[64,2048,1408]{2,1,0:T(8,128)(2,1)} fusion(%a, %b), kind=kOutput, calls=%fused_computation.1
  %w = f32[128,256]{1,0} parameter(1)
  %x = f32[8,128]{1,0} parameter(2)
  %dot.5 = f32[8,256]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[]{:T(128)}, bf16[64,960,2048]{2,1,0}, bf16[64,1408,960]{1,2,0}) tuple(%i, %a, %b)
}

%cond.6 (arg: (s32[], bf16[64,960,2048], bf16[64,1408,960])) -> pred[] {
  %arg = (s32[]{:T(128)}, bf16[64,960,2048]{2,1,0}, bf16[64,1408,960]{1,2,0}) parameter(0)
  %constant.7 = s32[]{:T(128)} constant(3)
  %i = s32[]{:T(128)} get-tuple-element(%arg), index=0
  ROOT %lt = pred[]{:T(512)} compare(%i, %constant.7), direction=LT
}

ENTRY %main.8 (p: (s32[], bf16[64,960,2048], bf16[64,1408,960])) -> (s32[], bf16[64,960,2048], bf16[64,1408,960]) {
  %p = (s32[]{:T(128)}, bf16[64,960,2048]{2,1,0}, bf16[64,1408,960]{1,2,0}) parameter(0)
  ROOT %while.9 = (s32[]{:T(128)}, bf16[64,960,2048]{2,1,0}, bf16[64,1408,960]{1,2,0}) while(%p), condition=%cond.6, body=%body.3
}
"""


def test_hlo_counter_reads_tpu_batched_matmuls_and_loop_trips():
    import hlo_flops
    per_trip = 2 * 64 * 2048 * 1408 * 960 + 2 * 8 * 256 * 128
    assert hlo_flops.trip_counts(TPU_HLO) == [3]
    assert hlo_flops.matmul_flops(TPU_HLO) == 3 * per_trip
