"""Kimi-Linear 48B-A3B — KDA (gated delta-rule linear attention) : NoPE MLA
3:1, 256 experts top-8 with a sigmoid router plus 1 shared [arXiv:2510.26692]."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="kimi-linear-48b-a3b",
    family="hybrid",
    num_layers=27,
    d_model=2304,
    num_heads=32,
    num_kv_heads=32,
    d_ff=9216,
    vocab_size=163840,
    activation="swiglu",
    norm_eps=1e-5,
    attn_every=4,            # layers 4, 8, ..., 24 (1-based) are MLA
    attn_offset=3,
    attn_extra=(26,),        # and the last, layer 27
    head_dim=128,            # KDA heads (MLA sets its own head sizes)
    linear_mixer="kda",
    ssm_conv=4,
    use_mla=True,
    mla_rope=False,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    num_experts=256,
    top_k=8,
    num_shared_experts=1,
    moe_d_ff=1024,
    first_dense=1,
    router_act="sigmoid",
    routed_scale=2.446,
)
