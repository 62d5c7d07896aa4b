"""moonshot-v1-16b-a3b, alias moonlight-16b-a3b [moe] — the DeepSeek-V3
architecture (``model_type`` ``deepseek_v3``): multi-head latent attention
with no q LoRA, one leading dense layer, then 26 layers of 64 routed
experts (top 6, sigmoid scores with a selection bias, normalised, scaled by
2.446) beside 2 shared experts.
[hf:moonshotai/Moonlight-16B-A3B config.json; arXiv:2502.16982]

``CHIP`` is one chip's share of a deployment in which each layer's experts
are divided over 8 chips: the dense layer and the first 4 expert layers
(the others lie on further pipeline stages), experts 0-7 of each, and the
first eighth of the vocabulary, at published widths, trained one
microbatch a step.
"""
import dataclasses

from .base import ArchConfig

CONFIG = ArchConfig(
    name="moonshot-v1-16b-a3b",
    family="moe",
    source="hf:moonshotai/Moonlight-16B-A3B",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    d_ff_expert=1408,
    n_experts=64,
    top_k=6,
    n_shared_experts=2,
    router_score="sigmoid",
    topk_method="noaux_tc",
    routed_scaling=2.446,
    first_k_dense=1,
    router_aux_coef=0.0,              # seq_aux's coefficient is not given
    vocab_size=163840,
    activation="silu",
    gated_mlp=True,
    rope_theta=50_000.0,
    norm_eps=1e-5,
    max_seq=8192,
    microbatches=4,
    fsdp=True,
)

CHIP = dataclasses.replace(
    CONFIG.chip_share("moonlight-16b-a3b.chip", layers=5, experts_held=8,
                      vocab_size=20480),
    microbatches=1, fsdp=False)
