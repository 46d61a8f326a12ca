"""Config-system invariants: stack plans, layer patterns, shape applicability."""
import pytest

from repro.configs import (ALL_ARCHS, SHAPES, applicable_shapes, get_config,
                           reduced)


def test_all_archs_resolve():
    assert len(ALL_ARCHS) == 11
    for name in ALL_ARCHS:
        cfg = get_config(name)
        assert cfg.name == name


def test_stack_plan_covers_all_layers():
    for name in ALL_ARCHS:
        cfg = get_config(name)
        plan = cfg.stack_plan()
        total = cfg.first_dense + sum(s.num_layers for s in plan) - cfg.first_dense
        assert sum(s.num_layers for s in plan) == cfg.num_layers, name


def test_jamba_pattern():
    cfg = get_config("jamba-v0.1-52b")
    specs = [cfg.layer_spec(i) for i in range(cfg.num_layers)]
    attn_layers = [i for i, s in enumerate(specs) if s.mixer == "attn"]
    assert attn_layers == [3, 11, 19, 27]          # 1:7 interleave
    moe_layers = [i for i, s in enumerate(specs) if s.ffn == "moe"]
    assert moe_layers == list(range(1, 32, 2))     # every other layer
    assert all(s.mixer == "mamba" for i, s in enumerate(specs)
               if i not in attn_layers)


def test_gemma3_pattern():
    cfg = get_config("gemma3-4b")
    specs = [cfg.layer_spec(i) for i in range(cfg.num_layers)]
    # 5 local : 1 global
    for i, s in enumerate(specs):
        if i % 6 == 5:
            assert s.window is None, i
        else:
            assert s.window == cfg.window_size, i


def test_deepseek_v2_first_dense():
    cfg = get_config("deepseek-v2-lite-16b")
    specs = [cfg.layer_spec(i) for i in range(cfg.num_layers)]
    assert specs[0].ffn == "dense"
    assert all(s.ffn == "moe" for s in specs[1:])
    assert all(s.mixer == "mla" for s in specs)


def test_xlstm_pattern():
    cfg = get_config("xlstm-125m")
    specs = [cfg.layer_spec(i) for i in range(cfg.num_layers)]
    assert [s.mixer for s in specs[:4]] == ["mlstm"] * 3 + ["slstm"]


def test_applicable_shapes():
    long_ok = {n for n in ALL_ARCHS
               if "long_500k" in applicable_shapes(get_config(n))}
    assert long_ok == {"gemma2-9b", "gemma3-4b", "xlstm-125m", "jamba-v0.1-52b"}
    for n in ALL_ARCHS:
        shapes = applicable_shapes(get_config(n))
        assert {"train_4k", "prefill_32k", "decode_32k"} <= set(shapes)


def test_reduced_bounds():
    for name in ALL_ARCHS:
        r = reduced(get_config(name))
        assert r.d_model <= 512
        assert r.num_layers <= 8
        if r.num_experts:
            assert r.num_experts <= 4
        assert r.family == get_config(name).family


def test_padded_vocab_divisible():
    for name in ALL_ARCHS:
        cfg = get_config(name)
        assert cfg.padded_vocab % 16 == 0
        assert cfg.padded_vocab >= cfg.vocab_size


def test_param_counts_plausible():
    import re
    from repro.models import count_params
    expected = {"deepseek-67b": 67e9, "gemma2-9b": 10e9, "gemma-2b": 2.5e9,
                "qwen3-moe-30b-a3b": 30e9, "jamba-v0.1-52b": 52e9,
                "chameleon-34b": 34e9, "deepseek-v2-lite-16b": 16e9,
                "gemma3-4b": 4.5e9}
    for name, target in expected.items():
        n = count_params(get_config(name))
        assert 0.8 * target < n < 1.25 * target, (name, n)
