"""deepseek-v2-lite [moe] — 27L d_model=2048, multi-head latent attention
(16 heads; queries 128 + 64 rotary wide, uncompressed; keys and values from
a 512-wide latent with a 64-wide rotary key part shared by the heads; values
128), YaRN RoPE (base 10000, factor 40 over 4096 positions); vocab=102400;
layer 0 a dense SwiGLU FFN of 10944, the other 26 MoE: 2 shared + 64 routed
experts of 1408, top-6 over a softmax, not renormalised; RMSNorm eps 1e-6.
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite]"""
from repro.configs.base import ModelConfig
from repro.core.dsg_linear import DSGConfig

ARCH_ID = "deepseek-v2-lite"


def config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID, family="moe", n_layers=27, d_model=2048,
        n_heads=16, n_kv=16, d_ff=10944, vocab=102400, d_head=192,
        rope_theta=10_000.0, norm_eps=1e-6, dtype="bfloat16",
        kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
        rope_factor=40.0, rope_original_max=4096, rope_beta_fast=32.0,
        rope_beta_slow=1.0, rope_mscale=0.707, rope_mscale_all_dim=0.707,
        microbatches=2, moe_aux="probs",
        moe_experts=64, moe_topk=6, moe_shared=2, moe_d_ff=1408,
        moe_norm_topk=False, n_dense_layers=1,
        dsg=DSGConfig(enabled=True, gamma=0.5, eps=0.5, block=128,
                      threshold_mode="shared", mode="mask", n_chunks=16),
    )


def smoke_config() -> ModelConfig:
    """Two MoE layers after one dense layer at test widths (latent 32,
    rotary 16, YaRN over 64 original positions); this chip holds experts
    2-5 of 8."""
    return config().replace(
        n_layers=3, d_model=64, n_heads=4, n_kv=4, d_ff=192, vocab=256,
        d_head=24, dtype="float32",
        kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        rope_original_max=64,
        moe_experts=8, moe_topk=3, moe_shared=1, moe_d_ff=128,
        moe_expert_offset=2, moe_experts_held=4,
        dsg=DSGConfig(enabled=True, gamma=0.5, eps=0.5, block=64,
                      threshold_mode="shared", mode="mask", n_chunks=1))
