"""Work the configuration's arithmetic requires, from widths and true lengths.

Every count here comes from the configuration's published widths and the
traffic's unpadded lengths, never from a kernel's grid, padding or bucket, so a
share of a roofline or of a peak stays under 100% whatever implements the layer.
Counts are per decode step or per prefill, summed by the caller over the steps
of a traced window.  `depth` is the number of keys a lane's query attends to:
its prompt plus the tokens generated so far, the new one included.
"""
from __future__ import annotations

import math

BYTES = {"bfloat16": 2, "float32": 4}


def _dims(cfg: dict):
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"])


def keep_share(cfg: dict) -> float:
    """Share of FFN neuron groups DSG keeps (1.0 when DSG is off)."""
    dsg = cfg["dsg"]
    if not dsg["enabled"]:
        return 1.0
    g = cfg["intermediate_size"] // dsg["block"]
    return kept_groups(cfg) / g


def kept_groups(cfg: dict) -> int:
    dsg = cfg["dsg"]
    g = cfg["intermediate_size"] // dsg["block"]
    return max(1, math.ceil((1.0 - dsg["gamma"]) * g - 1e-9))


def weight_flops_per_token(cfg: dict) -> float:
    """Two FLOPs per weight a token multiplies: attention projections, the
    FFN at its kept share, and the output head (the embedding is a lookup)."""
    L, d, H, kv, hd, f, v = _dims(cfg)
    attn = d * (H + 2 * kv) * hd + H * hd * d
    ffn = 3 * d * f * keep_share(cfg)
    return 2.0 * (L * (attn + ffn) + d * v)


def attn_flops(cfg: dict, depth_sum: float) -> float:
    """QK^T and PV over `depth_sum` keys in all, every layer."""
    L, _, H, _, hd, _, _ = _dims(cfg)
    return 4.0 * L * H * hd * depth_sum


def drs_flops(cfg: dict, rows: float) -> float:
    """DRS scoring of `rows` FFN inputs in every layer: the projection
    h R^T and the virtual product with R W_gate."""
    dsg = cfg["dsg"]
    if not dsg["enabled"]:
        return 0.0
    L, d, _, _, _, f, _ = _dims(cfg)
    k = dsg["proj_dim"]
    return 2.0 * L * rows * (d * k + k * f)


def decode_flops(cfg: dict, lanes: float, depth_sum: float,
                 refresh_lanes: float = 0.0) -> float:
    """One or more decode steps: `lanes` tokens, `depth_sum` keys attended,
    and `refresh_lanes` lanes that DSG re-scores."""
    return (lanes * weight_flops_per_token(cfg) + attn_flops(cfg, depth_sum)
            + drs_flops(cfg, refresh_lanes))


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """One prompt of `prompt_len` tokens under causal attention; DSG scores
    every prompt token."""
    p = prompt_len
    return (p * weight_flops_per_token(cfg) + attn_flops(cfg, p * (p + 1) / 2)
            + drs_flops(cfg, p))


def attn_bytes(cfg: dict, lanes: float, depth_sum: float) -> float:
    """Paged decode attention: K and V of every key attended, the new K and
    V written, and each lane's query read and output written, every layer."""
    L, _, H, kv, hd, _, _ = _dims(cfg)
    b = BYTES[cfg["torch_dtype"]]
    return L * b * (2 * kv * hd * depth_sum
                    + lanes * (2 * kv * hd + 2 * H * hd))


def ffn_csr_flops(cfg: dict, lanes: float) -> float:
    """Sparse FFN of `lanes` tokens: each lane's kept groups of the three
    matrices, every layer."""
    L, d, _, _, _, _, _ = _dims(cfg)
    blk = cfg["dsg"]["block"]
    return 2.0 * L * lanes * kept_groups(cfg) * blk * d * 3


def ffn_csr_bytes(cfg: dict, steps: float, lanes: float) -> float:
    """Lower bound on the sparse FFN's bytes: one lane's kept groups of the
    three matrices per layer and step (no selection can read less; the
    union over lanes is larger), plus each lane's input and output row."""
    L, d, _, _, _, _, _ = _dims(cfg)
    b = BYTES[cfg["torch_dtype"]]
    blk = cfg["dsg"]["block"]
    return L * b * (steps * kept_groups(cfg) * blk * d * 3 + lanes * 2 * d)


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of compute and
    memory time at the published peaks."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
