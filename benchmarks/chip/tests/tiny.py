"""A tiny cell of the DeepSeek-V2 configuration, for runs on the CPU."""
import copy
import json
import os

from harness import HERE, Cell

DEEPSEEK = {
    "reference": "deepseek_v2", "vocab_key": "vocab_size",
    "hidden_size": 64, "num_attention_heads": 4, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
    "q_lora_rank": None, "num_hidden_layers": 2, "first_k_dense_replace": 1,
    "intermediate_size": 192, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "n_shared_experts": 2,
    "vocab_size": 256, "rope_theta": 10000, "rms_norm_eps": 1e-6,
    "capacity_factor": 1.25, "aux_coef": 0.01,
    "arch": "deepseek-v2-lite-16b",
    "overrides": {"d_model": 64, "num_heads": 4, "num_kv_heads": 4,
                  "qk_nope_dim": 16, "qk_rope_dim": 8, "v_head_dim": 16,
                  "kv_lora_rank": 32, "num_layers": 2, "d_ff": 192,
                  "moe_d_ff": 32, "num_experts": 8, "top_k": 2,
                  "vocab_size": 256},
}


def job(name, **kw):
    with open(os.path.join(HERE, "jobs", f"{name}.json")) as f:
        j = json.load(f)
    j.update(kw)
    return j


def cell(config, job_, limits=None, f32=False):
    config = copy.deepcopy(config)
    if f32:
        config["overrides"].update(param_dtype="float32",
                                   compute_dtype="float32")
    lim = {"limits": limits or {"loss_gap": None, "grad_gap": None,
                                "delta_gap": None}}
    return Cell("tiny", job_["chips"], config, job_, lim, [], [])
