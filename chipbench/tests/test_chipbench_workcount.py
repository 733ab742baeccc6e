"""Work counts and peaks against hand-computed numbers (internlm2-1.8b)."""
import json
from pathlib import Path

import pytest

from chipbench import device, workcount as wc

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
DENSE = json.loads((CONFIGS / "internlm2-1.8b.json").read_text())
DSG = json.loads((CONFIGS / "internlm2-1.8b-dsg50.json").read_text())
V5E = device.peaks_for("TPU v5 lite")

# per layer: q, k, v, o = 2048 x (16 + 8 + 8) x 128 + 16 x 128 x 2048
ATTN_W = 2048 * 32 * 128 + 16 * 128 * 2048          # 12,582,912
FFN_W = 3 * 2048 * 8192                              # 50,331,648
HEAD_W = 2048 * 92544                                # 189,530,112


def test_flops_per_token_dense_and_dsg():
    assert wc.weight_flops_per_token(DENSE) == 2 * (24 * (ATTN_W + FFN_W)
                                                    + HEAD_W)
    assert wc.weight_flops_per_token(DENSE) == 3_398_959_104
    # DSG keeps 32 of 64 groups: half the FFN weights
    assert wc.weight_flops_per_token(DSG) == 2 * (24 * (ATTN_W + FFN_W // 2)
                                                  + HEAD_W)
    assert wc.weight_flops_per_token(DSG) == 2_190_999_552


def test_attention_at_true_depths():
    # two lanes at depths 100 and 200: 300 keys in all
    assert wc.attn_flops(DENSE, 300) == 4 * 24 * 16 * 128 * 300
    kv_bytes = 2 * 8 * 128 * 300                     # K and V of every key
    per_lane = 2 * 8 * 128 + 2 * 16 * 128            # new K, V; q and out
    assert wc.attn_bytes(DENSE, 2, 300) == 24 * 2 * (kv_bytes + 2 * per_lane)
    assert wc.attn_bytes(DENSE, 2, 300) == 30_081_024


def test_csr_ffn_flops_and_lower_bound_bytes():
    assert wc.kept_groups(DSG) == 32 and wc.keep_share(DSG) == 0.5
    assert wc.ffn_csr_flops(DSG, 32) == 2 * 24 * 32 * 32 * 128 * 2048 * 3
    # one lane's kept groups of three matrices, each layer, plus the rows
    assert wc.ffn_csr_bytes(DSG, 1, 32) == 24 * 2 * (32 * 128 * 2048 * 3
                                                     + 32 * 2 * 2048)
    assert wc.ffn_csr_bytes(DSG, 1, 32) == 1_214_251_008


def test_drs_and_prefill_and_decode_totals():
    assert wc.drs_flops(DENSE, 5) == 0.0
    assert wc.drs_flops(DSG, 1) == 2 * 24 * (2048 * 256 + 256 * 8192)
    assert wc.prefill_flops(DENSE, 2) == (2 * 3_398_959_104
                                          + 4 * 24 * 16 * 128 * 3)
    assert wc.decode_flops(DSG, 3, 600, 1) == (
        3 * 2_190_999_552 + 4 * 24 * 16 * 128 * 600 + wc.drs_flops(DSG, 1))


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        device.peaks_for("TPU v99 imaginary")
    assert V5E["bf16_flops_per_s"] == 197e12
    assert V5E["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("flops,nbytes", [
    (wc.decode_flops(DENSE, 32, 32 * 350), 3.8e9),
    (wc.attn_flops(DENSE, 32 * 350), wc.attn_bytes(DENSE, 32, 32 * 350)),
    (wc.ffn_csr_flops(DSG, 32), wc.ffn_csr_bytes(DSG, 1, 32)),
])
def test_share_cannot_pass_100_at_peak(flops, nbytes):
    """A device running at its published peaks needs at least the least
    time for the counted work, so its share is at most 100%."""
    least = wc.least_seconds(flops, nbytes, V5E)
    assert least >= flops / V5E["bf16_flops_per_s"]
    assert least >= nbytes / V5E["hbm_bytes_per_s"]
    for slower in (1.0, 1.5, 10.0):
        assert 100.0 * least / (least * slower) <= 100.0
