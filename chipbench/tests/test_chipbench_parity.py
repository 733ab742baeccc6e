"""Parity with the commit before models became modules: the model module
`chipbench/models/internlm2.py` and the generic harness around it give the
same weights bits, reference gaps, `selection_miss` and work counts as the
InternLM2 code that `weights.py`, `reference.py` and `workcount.py` held
before (commit 34efaae).  Every constant below was computed at that commit,
on the CPU, from the same calls; nothing here is rounded."""
import hashlib
import json

import jax
import numpy as np
import pytest

from chipbench import bench, reference, workcount as wc
from chipbench.tests import tiny
from chipbench.weights import make_weights

CONFIGS = tiny.PKG / "configs"


def config(name):
    path = tiny.DATA / f"{name}.json"
    if not path.exists():
        path = CONFIGS / f"{name}.json"
    return json.loads(path.read_text())


#: sha256 of dtype, shape and bytes of every leaf of make_weights
WEIGHTS = {
    ("tiny", 7): {
        "['embed']":
            "1ea6ea43c21568bbe3a4d9ccf95812ff9cecdb8b29201948bfa25b7084ce0f55",
        "['layers']['attn']['wk']":
            "be59492c724d64e98855456701e14534bdc232ef490678e57042c5f8389694ed",
        "['layers']['attn']['wo']":
            "5bedaba0e62c949ace9347af89dbe4a26a4608c1f5a0ee26f50377391baedcdd",
        "['layers']['attn']['wq']":
            "caf136aa58cfe77d399e7d24cf3b1d6c3e2fdb325177279598c08e428387d554",
        "['layers']['attn']['wv']":
            "a75a7184ae9df7ff7799f0a1bb1a007ae166d6f1c9da6dc1fc295ff0428bdf27",
        "['layers']['ffn']['w_down']":
            "25065f6a471b244e99455d86020b41f161f8abcbc16a510c896f2849ee4a83dd",
        "['layers']['ffn']['w_gate']":
            "6082b3faf777433db190ea6c842152a36a20c67e1da97debec5141b9370eef65",
        "['layers']['ffn']['w_up']":
            "789d59d162f6080d5ebcb41a4164ae1e179b9339ac814742a55beefd13f2555c",
        "['layers']['ln_attn']['scale']":
            "6bfaa2152702ba6dedd5d6577f08b304e90898a0a41603c45149c91586e89fe5",
        "['layers']['ln_ffn']['scale']":
            "d1bce9dece5f8ffa6bae9007cd9c8ea702fc75f0af56a11b05638578ab20651f",
        "['lm_head']":
            "6e067add640b5d8eba3f6abed719cfc287315be64102781f812e18808e7be7db",
        "['ln_final']['scale']":
            "1c3d3eea7c5ac940ca2a8710b4ac7f5b933eda93cf377897a814dbb0163c40f5",
    },
    ("tiny", 2147483653): {
        "['embed']":
            "b2beedb380e85368afeabd1d2465a2f1f88752e9b437afeaf86556800ab07201",
        "['layers']['attn']['wk']":
            "c2d756b4243abab34e99e7bf21844e62ef06e17da19a94570957b5015a882ecc",
        "['layers']['attn']['wo']":
            "49ff1d138af86a5f63c47b106db6e2a71f6620331400689b9968ce8a0f762e23",
        "['layers']['attn']['wq']":
            "a8ba33e192daa431003bb9964b172c50beb14aba799b87a77f5649484e10d4bc",
        "['layers']['attn']['wv']":
            "cedda78248fbf946307f0d0b029269abc975da3a0da4a1f1f12dc6e6ef3958d2",
        "['layers']['ffn']['w_down']":
            "15a81906ebb9ea3d96c34f6dac473efe2c7f5e6d69568e2758ea0e2f058bccb5",
        "['layers']['ffn']['w_gate']":
            "2988ff5548226786365ba44dcdac14221de0c487be7960a93d3358517bfcae6f",
        "['layers']['ffn']['w_up']":
            "0c02e7f868978ccd470af6b5fca5fc324781a1cbee159c4161b7e193d36b42ca",
        "['layers']['ln_attn']['scale']":
            "322591b0c57d2a067adf8d24db1f6f8032d7a430e51573ffdfe26b9e48c9db2c",
        "['layers']['ln_ffn']['scale']":
            "3e2fdc07103c70a216f7cdcdfd2fdc46b204e1adcf13532e54f690d66853c29d",
        "['lm_head']":
            "5fbb9f5c5c27e777c69084d34105b6925c4b9ede2ed3b0484d65e31d7114e628",
        "['ln_final']['scale']":
            "b582b7aafcdbe05c0a68b9d6b390c210f8da00667b64bb79bf90c0e38140e914",
    },
    ("tiny-dsg", 7): {
        "['embed']":
            "1ea6ea43c21568bbe3a4d9ccf95812ff9cecdb8b29201948bfa25b7084ce0f55",
        "['layers']['attn']['wk']":
            "be59492c724d64e98855456701e14534bdc232ef490678e57042c5f8389694ed",
        "['layers']['attn']['wo']":
            "5bedaba0e62c949ace9347af89dbe4a26a4608c1f5a0ee26f50377391baedcdd",
        "['layers']['attn']['wq']":
            "caf136aa58cfe77d399e7d24cf3b1d6c3e2fdb325177279598c08e428387d554",
        "['layers']['attn']['wv']":
            "a75a7184ae9df7ff7799f0a1bb1a007ae166d6f1c9da6dc1fc295ff0428bdf27",
        "['layers']['ffn']['w_down']":
            "25065f6a471b244e99455d86020b41f161f8abcbc16a510c896f2849ee4a83dd",
        "['layers']['ffn']['w_gate']":
            "6082b3faf777433db190ea6c842152a36a20c67e1da97debec5141b9370eef65",
        "['layers']['ffn']['w_up']":
            "789d59d162f6080d5ebcb41a4164ae1e179b9339ac814742a55beefd13f2555c",
        "['layers']['ln_attn']['scale']":
            "6bfaa2152702ba6dedd5d6577f08b304e90898a0a41603c45149c91586e89fe5",
        "['layers']['ln_ffn']['scale']":
            "d1bce9dece5f8ffa6bae9007cd9c8ea702fc75f0af56a11b05638578ab20651f",
        "['lm_head']":
            "6e067add640b5d8eba3f6abed719cfc287315be64102781f812e18808e7be7db",
        "['ln_final']['scale']":
            "1c3d3eea7c5ac940ca2a8710b4ac7f5b933eda93cf377897a814dbb0163c40f5",
        "['r']":
            "faa580580711466851e3da445686493e7923e9caf2cef3a0dddaaafaa4c7abc7",
    },
    ("tiny-dsg", 2147483653): {
        "['embed']":
            "b2beedb380e85368afeabd1d2465a2f1f88752e9b437afeaf86556800ab07201",
        "['layers']['attn']['wk']":
            "c2d756b4243abab34e99e7bf21844e62ef06e17da19a94570957b5015a882ecc",
        "['layers']['attn']['wo']":
            "49ff1d138af86a5f63c47b106db6e2a71f6620331400689b9968ce8a0f762e23",
        "['layers']['attn']['wq']":
            "a8ba33e192daa431003bb9964b172c50beb14aba799b87a77f5649484e10d4bc",
        "['layers']['attn']['wv']":
            "cedda78248fbf946307f0d0b029269abc975da3a0da4a1f1f12dc6e6ef3958d2",
        "['layers']['ffn']['w_down']":
            "15a81906ebb9ea3d96c34f6dac473efe2c7f5e6d69568e2758ea0e2f058bccb5",
        "['layers']['ffn']['w_gate']":
            "2988ff5548226786365ba44dcdac14221de0c487be7960a93d3358517bfcae6f",
        "['layers']['ffn']['w_up']":
            "0c02e7f868978ccd470af6b5fca5fc324781a1cbee159c4161b7e193d36b42ca",
        "['layers']['ln_attn']['scale']":
            "322591b0c57d2a067adf8d24db1f6f8032d7a430e51573ffdfe26b9e48c9db2c",
        "['layers']['ln_ffn']['scale']":
            "3e2fdc07103c70a216f7cdcdfd2fdc46b204e1adcf13532e54f690d66853c29d",
        "['lm_head']":
            "5fbb9f5c5c27e777c69084d34105b6925c4b9ede2ed3b0484d65e31d7114e628",
        "['ln_final']['scale']":
            "b582b7aafcdbe05c0a68b9d6b390c210f8da00667b64bb79bf90c0e38140e914",
        "['r']":
            "beaad88d4598c77070373f373704d6a63cd53d53be4c9cd90c4622aaba66d1ac",
    },
}

#: reference.compare(control=True) on `fixed_rows`, weights of seed 7
COMPARE = {"tiny": {"control": {"mean_gap": 0.0011232823133468627,
                                "not_first": 0.0375,
                                "widest_gap": 0.04654860496520996},
                    "program": {"mean_gap": 3.5008044242858887,
                                "not_first": 1.0,
                                "widest_gap": 5.203765869140625},
                    "tokens": 80},
           "tiny-dsg": {"control": {"mean_gap": 0.4660351872444153,
                                    "not_first": 0.7125,
                                    "selection_miss": 0.33421969413757324,
                                    "widest_gap": 2.001100778579712},
                        "program": {"mean_gap": 3.438770580291748,
                                    "not_first": 1.0,
                                    "selection_miss": 0.6808441281318665,
                                    "widest_gap": 5.883148193359375},
                        "tokens": 80}}

#: decode at 32 lanes over 11,200 keys (4 DSG refreshes), a 96-token
#: prefill, attention bytes at 32 lanes, the CSR FFN at 32 lanes and
#: 3 steps
WORK = {"internlm2-1.8b": {"attn_bytes": 1110441984,
                           "decode_flops": 110968700928.0,
                           "ffn_csr_bytes": None,
                           "ffn_csr_flops": None,
                           "prefill_flops": 327215480832.0},
        "internlm2-1.8b-dsg50": {"attn_bytes": 1110441984,
                                 "decode_flops": 72817311744.0,
                                 "ffn_csr_bytes": 3630170112,
                                 "ffn_csr_flops": 38654705664.0,
                                 "prefill_flops": 223330959360.0},
        "tiny": {"attn_bytes": 5783552,
                 "decode_flops": 76480512.0,
                 "ffn_csr_bytes": None,
                 "ffn_csr_flops": None,
                 "prefill_flops": 199802880.0},
        "tiny-dsg": {"attn_bytes": 5783552,
                     "decode_flops": 65208320.0,
                     "ffn_csr_bytes": 1212416,
                     "ffn_csr_flops": 12582912.0,
                     "prefill_flops": 193511424.0}}


def leaf_hashes(w):
    out = {}
    for path, a in jax.tree_util.tree_flatten_with_path(w)[0]:
        a = np.asarray(a)
        h = hashlib.sha256(f"{a.dtype}{a.shape}".encode() + a.tobytes())
        out[jax.tree_util.keystr(path)] = h.hexdigest()
    return out


def fixed_rows(cfg):
    """Two rows of (prompt, served tokens) from a fixed seed and, for DSG,
    their selections on the engine's schedule with random kept groups."""
    rng = np.random.default_rng(20261017)
    v = cfg["vocab_size"]
    rows = [(rng.integers(0, v, p).astype(np.int32),
             rng.integers(0, v, n).tolist()) for p, n in ((17, 30), (40, 50))]
    if not cfg["dsg"]["enabled"]:
        return rows, None
    layers = cfg["num_hidden_layers"]
    groups = bench.model(cfg).dsg_groups(cfg)
    keep = reference.dsg_keep(cfg)
    refresh = cfg["dsg"]["refresh_interval"]
    sels = []
    for prompt, out in rows:
        p, n = len(prompt), len(out)
        srcs = [p - 1] + [p - 1 + refresh * j
                          for j in range(1, (n - 1) // refresh + 1)]
        sel = []
        for j, s in enumerate(srcs):
            kept = np.zeros((layers, groups), bool)
            for li in range(layers):
                kept[li, rng.permutation(groups)[:keep]] = True
            sel.append((s, s if j == 0 else s + 1, kept))
        sels.append(sel)
    return rows, sels


@pytest.mark.parametrize("name,seed", sorted(WEIGHTS))
def test_weights_bits(name, seed):
    assert leaf_hashes(make_weights(config(name), seed)) == WEIGHTS[
        (name, seed)]


@pytest.mark.parametrize("name", sorted(COMPARE))
def test_reference_gaps_and_selection_miss(name):
    cfg = config(name)
    rows, sels = fixed_rows(cfg)
    got = reference.compare(cfg, make_weights(cfg, 7), rows, n_rows=3,
                            control=True, selections=sels)
    assert got == COMPARE[name]


@pytest.mark.parametrize("name", sorted(WORK))
def test_work_counts(name):
    cfg = config(name)
    dsg = cfg["dsg"]["enabled"]
    assert {
        "decode_flops": wc.decode_flops(cfg, 32, 32 * 350, 4 if dsg else 0),
        "prefill_flops": wc.prefill_flops(cfg, 96),
        "attn_bytes": wc.attn_bytes(cfg, 32, 32 * 350),
        "ffn_csr_flops": wc.ffn_csr_flops(cfg, 32) if dsg else None,
        "ffn_csr_bytes": wc.ffn_csr_bytes(cfg, 3, 32) if dsg else None,
    } == WORK[name]
