"""kimi-vl-a3b [vlm] — Kimi-VL-A3B-Instruct's language model
[arXiv:2504.07491; hf moonshotai/Kimi-VL-A3B-Instruct config.json].

27L d_model=2048 16H MLA (kv_lora_rank 512, no q LoRA, qk 128 nope + 64
rope, v 128), rope theta 800k; layer 0 dense (d_ff 11264), layers 1-26
MoE: 64 routed experts of 1408, 6 a token by sigmoid score plus a
correction bias (noaux_tc, one group), weights normalized and scaled by
2.446, 2 shared experts; vocab 163840, untied.  The MoonViT tower is a
STUB: 64 image tokens a 224x224 crop (14-px patches, 2x2 merge) from
``patchify_stub``, followed by ``text_tokens`` caption tokens.
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="kimi-vl-a3b",
    family="vlm",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    vocab_size=163_840,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(n_experts=64, top_k=6, n_shared=2, d_ff_expert=1408,
                  router="sigmoid", routed_scaling=2.446,
                  aux_loss_weight=1e-3),
    first_dense_layers=1,
    rope_theta=800_000.0,
    norm_eps=1e-5,
    frontend="vision_stub",
    frontend_tokens=64,
    text_tokens=960,
    source="arXiv:2504.07491; hf",
)


def reduced() -> ModelConfig:
    import dataclasses
    return dataclasses.replace(
        CONFIG, n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=96,
        vocab_size=512,
        mla=MLAConfig(kv_lora_rank=16, qk_nope_head_dim=8,
                      qk_rope_head_dim=4, v_head_dim=8),
        moe=dataclasses.replace(CONFIG.moe, n_experts=16, top_k=4,
                                d_ff_expert=32, n_held=4),
        frontend_tokens=8, text_tokens=24)
