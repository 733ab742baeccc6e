"""Work the configuration's arithmetic requires, from widths and true lengths.

Every count here comes from the configuration's published widths and the
traffic's unpadded lengths, never from a kernel's grid, padding or bucket, so a
share of a roofline or of a peak stays under 100% whatever implements the layer.
Counts are per decode step or per prefill, summed by the caller over the steps
of a traced window.  `depth` is the number of keys a lane's query attends to:
its prompt plus the tokens generated so far, the new one included.

What a model's layers require (weights a token multiplies, attention at a
depth, the sparse FFN and DRS) is its model module's (`bench.model`); the
functions of that name here hand the configuration to it.  What is the same
for every model stays here: DSG's kept share of groups, a decode step and a
prefill composed from those counts, and the least time at the peaks.
"""
from __future__ import annotations

import math

from chipbench import bench

BYTES = {"bfloat16": 2, "float32": 4}


def kept_of(groups: int, gamma: float) -> int:
    """Groups DSG keeps of `groups` at sparsity `gamma`:
    ceil((1 - gamma) groups)."""
    return max(1, math.ceil((1.0 - gamma) * groups - 1e-9))


def keep_share(cfg: dict) -> float:
    """Share of FFN neuron groups DSG keeps (1.0 when DSG is off)."""
    if not cfg["dsg"]["enabled"]:
        return 1.0
    return kept_groups(cfg) / bench.model(cfg).dsg_groups(cfg)


def kept_groups(cfg: dict) -> int:
    """Groups DSG keeps of a layer's `dsg_groups`."""
    return kept_of(bench.model(cfg).dsg_groups(cfg), cfg["dsg"]["gamma"])


def weight_flops_per_token(cfg: dict) -> float:
    """Two FLOPs per weight a token multiplies (the embedding is a lookup)."""
    return bench.model(cfg).weight_flops_per_token(cfg)


def attn_flops(cfg: dict, depth_sum: float) -> float:
    """Attention's FLOPs over `depth_sum` keys in all, every layer."""
    return bench.model(cfg).attn_flops(cfg, depth_sum)


def attn_bytes(cfg: dict, lanes: float, depth_sum: float) -> float:
    """Paged decode attention's bytes for `lanes` queries over `depth_sum`
    keys in all, every layer."""
    return bench.model(cfg).attn_bytes(cfg, lanes, depth_sum)


def drs_flops(cfg: dict, rows: float) -> float:
    """DRS scoring of `rows` FFN inputs in every layer (0 without DSG)."""
    return bench.model(cfg).drs_flops(cfg, rows)


def ffn_csr_flops(cfg: dict, lanes: float) -> float:
    """Sparse FFN of `lanes` tokens at their kept groups, every layer."""
    return bench.model(cfg).ffn_csr_flops(cfg, lanes)


def ffn_csr_bytes(cfg: dict, steps: float, lanes: float) -> float:
    """Lower bound on the sparse FFN's bytes over `steps` steps of `lanes`
    lanes in all, every layer."""
    return bench.model(cfg).ffn_csr_bytes(cfg, steps, lanes)


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


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of compute and
    memory time at the published peaks."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
