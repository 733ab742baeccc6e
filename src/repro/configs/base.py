"""Model/config schema shared by all architectures and the launcher."""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Tuple

from repro.core.dsg_linear import DSGConfig


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | xlstm | zamba | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    d_head: int = 0             # 0 -> d_model // n_heads
    rope_theta: float = 1_000_000.0
    act: str = "swiglu"         # swiglu | gelu
    norm: str = "rmsnorm"       # rmsnorm | layernorm
    norm_eps: float = 1e-6      # RMSNorm epsilon (the published rms_norm_eps)
    tie_embeddings: bool = False
    # --- MoE ---
    moe_experts: int = 0
    moe_topk: int = 0
    moe_shared: int = 0         # number of shared (always-on) experts
    moe_d_ff: int = 0           # per-expert hidden dim (fine-grained MoE)
    moe_capacity_factor: float = 1.25
    moe_norm_topk: bool = True  # renormalise the top-k router weights
    n_dense_layers: int = 0     # leading layers with a dense FFN instead
                                # (first_k_dense_replace), d_ff wide
    moe_expert_offset: int = 0  # the routed experts this chip holds:
    moe_experts_held: int = 0   # [offset, offset + held); 0 holds all
    # --- SSM / hybrid ---
    ssm_state: int = 0          # Mamba2 N
    ssm_expand: int = 2
    ssm_heads: int = 0          # Mamba2 heads (d_inner / head_dim)
    ssm_chunk: int = 128
    shared_attn_every: int = 0  # zamba: shared attn block every N mamba blocks
    slstm_every: int = 0        # xlstm: sLSTM block every N layers
    # --- encoder-decoder (whisper) ---
    enc_layers: int = 0
    dec_ratio: int = 8          # dec_len = seq_len // dec_ratio for enc-dec shapes
    # --- VLM ---
    vision_prefix: int = 0      # number of stub patch-embedding positions
    # --- attention ---
    window: int = 0             # sliding-window size (0 = full); used for
                                # sub-quadratic long-context variants
    attn_shard: str = "auto"    # "head" | "seq" | "auto" (head if
                                # n_heads % model_shards == 0, else seq)
    # --- DSG ---
    dsg: DSGConfig = field(default_factory=DSGConfig)
    # --- numerics / execution ---
    dtype: str = "float32"      # activation/param compute dtype
    remat: bool = True          # checkpoint each layer in training
    max_seq: int = 8192         # serving cache allocation default
    # --- perf levers (EXPERIMENTS.md §Perf) ---
    branch_constrain: bool = False   # force TP branch psums at bf16 branch
                                     # boundaries (not inside f32 norm bwd)
    moe_aux: str = "topk"            # "topk" | "probs" (sort-free aux loss)
    seq_sharded_residual: bool = False  # Megatron-SP style: residual stream
                                        # (and remat stash) sharded over seq
    gqa_native: bool = False         # grouped attention einsum instead of
                                     # materializing repeated KV heads
    attn_bf16_scores: bool = False   # QK^T scores and probabilities kept
                                     # bf16 (softmax stats stay f32) —
                                     # halves attention HBM traffic
    paged_attn_kernel: str = "auto"  # paged decode executor: "kernel"
                                     # (Pallas paged_attention, interpret
                                     # on CPU), "xla" (bounded gather
                                     # fallback), "auto" (kernel on TPU)
    dsg_ffn_apply: str = "auto"      # group-CSR serving FFN executor:
                                     # "dense" (masked-dense reference),
                                     # "xla" (bounded gather), "kernel"
                                     # (Pallas CSR walk), "auto" (kernel
                                     # on TPU) — see core/dsg_linear.swiglu_csr
    microbatches: int = 1            # gradient-accumulation microbatches
                                     # (remat stash lives per-microbatch:
                                     # peak activation memory / microbatches)

    @property
    def head_dim(self) -> int:
        return self.d_head or (self.d_model // self.n_heads)

    @property
    def is_moe(self) -> bool:
        return self.moe_experts > 0

    @property
    def moe_held(self) -> int:
        """Routed experts whose weights this chip holds."""
        return self.moe_experts_held or self.moe_experts

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""
    name: str                   # train_4k | prefill_32k | decode_32k | long_500k
    seq_len: int
    global_batch: int
    kind: str                   # "train" | "prefill" | "decode"


SHAPES: Tuple[ShapeConfig, ...] = (
    ShapeConfig("train_4k", 4096, 256, "train"),
    ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    ShapeConfig("decode_32k", 32768, 128, "decode"),
    ShapeConfig("long_500k", 524288, 1, "decode"),
)


def shape_by_name(name: str) -> ShapeConfig:
    for s in SHAPES:
        if s.name == name:
            return s
    raise KeyError(name)


# Smoke-test shape used by per-arch CPU smoke tests.
SMOKE_SHAPE = ShapeConfig("smoke", 32, 2, "train")
