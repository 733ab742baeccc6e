"""deepseek-moe-16b [moe] — 28L d_model=2048 16H (MHA, 16 KV heads of 128)
vocab=102400; layer 0 has a dense SwiGLU FFN of 10944, the other 27 are MoE:
2 shared + 64 routed experts of 1408, top-6 over a softmax, weights not
renormalised; RMSNorm eps 1e-6, RoPE base 10000.
[arXiv:2401.06066; huggingface.co/deepseek-ai/deepseek-moe-16b-base]"""
from repro.configs.base import ModelConfig
from repro.core.dsg_linear import DSGConfig

ARCH_ID = "deepseek-moe-16b"


def config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID, family="moe", n_layers=28, d_model=2048,
        n_heads=16, n_kv=16, d_ff=10944, vocab=102400, d_head=128,
        rope_theta=10_000.0, norm_eps=1e-6, dtype="bfloat16",
        attn_bf16_scores=True, microbatches=2, moe_aux="probs",
        moe_experts=64, moe_topk=6, moe_shared=2, moe_d_ff=1408,
        moe_norm_topk=False, n_dense_layers=1,
        dsg=DSGConfig(enabled=True, gamma=0.5, eps=0.5, block=128,
                      threshold_mode="shared", mode="mask", n_chunks=16),
    )


def smoke_config() -> ModelConfig:
    """Two MoE layers after one dense layer; this chip holds experts 2-5
    of 8."""
    return config().replace(
        n_layers=3, d_model=64, n_heads=4, n_kv=4, d_ff=192, vocab=256,
        d_head=16, dtype="float32",
        moe_experts=8, moe_topk=3, moe_shared=1, moe_d_ff=128,
        moe_expert_offset=2, moe_experts_held=4,
        dsg=DSGConfig(enabled=True, gamma=0.5, eps=0.5, block=64,
                      threshold_mode="shared", mode="mask", n_chunks=1))
