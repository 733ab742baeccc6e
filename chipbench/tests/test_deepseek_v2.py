"""The DeepSeek-V2 model module (`chipbench/models/deepseek_v2.py`) at test
size on the CPU: the engine's prefill-then-decode logits against the plain
reference, through the `mla_decode` kernel (interpret mode) and through the
XLA fallback; the program's YaRN against the reference's; the shares of an
expert-parallel deployment, for both DeepSeek model modules; `kv_rows` on the
engine's dispatch spans against the harness's depth sums; a whole run of a
tiny cell of it, come in by new files alone; its work counts and the readers
of its latent attention kernel.  The chip readings its limit was set from are
held against it by `test_chipbench_check.test_chip_readings_against_the_limits`
(conftest.py)."""
import json
import math
import shutil

import jax
import numpy as np
import pytest

from chipbench import bench, drivers, report, run, traffic
from chipbench.tests import tiny
from chipbench.weights import make_weights
from repro.serving.scheduler import Request

TINY = json.loads((tiny.DATA / "tiny-mla.json").read_text())
CELL = "tiny-mla.tiny_batch"
V2 = bench.load_model("deepseek_v2")
DS = bench.load_model("deepseek")
REAL = json.loads((bench.ROOT / "chipbench" / "configs"
                   / "deepseek-v2-lite-ep8.json").read_text())
REAL_CELL = "deepseek-v2-lite-ep8.long_decode_p512"


def config(**kw):
    return dict(TINY, **kw)


def add_cell(root):
    """The tiny MLA configuration and its cell, in the workloads of every
    metric the real V2 cell reports."""
    shutil.copy(tiny.DATA / "tiny-mla.json",
                root / "chipbench" / "configs" / "tiny-mla.json")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny-mla", "source": "test",
                         "file": "chipbench/configs/tiny-mla.json",
                         "reduced": ["n_routed_experts"], "why": "test"})
    b["workloads"].append({"name": CELL, "config": "tiny-mla",
                           "traffic": "tiny_batch", "chips": 1,
                           "why": "test"})
    real = bench.load()
    applies = {m["name"] for m in real["end_to_end"] + real["per_layer"]
               if REAL_CELL in m.get("workloads", ())}
    for m in b["end_to_end"] + b["per_layer"]:
        if m["name"] in applies:
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(b))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    with tiny.cache_settings_kept():
        root = tiny.make_root(tmp_path_factory.mktemp("tiny"))
        add_cell(root)
        yield root


# --------------------------------------------------------------------------
# the shares, for both model modules


SHARES = {"deepseek": (DS, json.loads(
              (tiny.DATA / "tiny-moe.json").read_text())),
          "deepseek_v2": (V2, TINY)}


@pytest.mark.parametrize("model_type", sorted(SHARES))
def test_shares_add_up_to_the_uncut_layer(model_type):
    """Four chips of four experts each (16 published): at one seed every
    share makes the same weights but its own experts, and the routed parts
    of all four, with the shared experts and attention counted once, are
    the layer that holds all sixteen."""
    mod, tiny_cfg = SHARES[model_type]
    seed = 2**31 + 3
    full_cfg = dict(tiny_cfg, n_routed_experts=16, expert_offset=0)
    full = make_weights(full_cfg, seed)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 128))
    key = mod._cfg_key(full_cfg)
    want = mod._moe_layer(x, full["layers"], 0, cfg_key=key, quant=False)
    without = dict(full["layers"], moe=dict(full["layers"]["moe"],
                   w_down=full["layers"]["moe"]["w_down"] * 0))
    base = mod._moe_layer(x, without, 0, cfg_key=key, quant=False)
    got = base
    for c in range(4):
        cfg = dict(tiny_cfg, expert_offset=4 * c)
        w = make_weights(cfg, seed)
        for k in ("w_gate", "w_up", "w_down"):    # its experts of the 16
            np.testing.assert_array_equal(
                np.asarray(w["layers"]["moe"][k]),
                np.asarray(full["layers"]["moe"][k][:, 4 * c:4 * c + 4]))
        got = got + mod._moe_layer(x, w["layers"], 0,
                                   cfg_key=mod._cfg_key(cfg),
                                   quant=False) - base
    # float32 sums in another order: rounding only
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


# --------------------------------------------------------------------------
# the program against the reference


def program_tests():
    """tests/test_moe_serving.py, whose `serve_logits` walks the engine's
    path and returns the logits it picks from."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "moe_serving_tests", bench.ROOT / "tests" / "test_moe_serving.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("route", ["kernel", "xla"])
def test_engine_logits_match_the_reference(route):
    """Prefill (plain form) then decode (absorbed form over the paged
    latent rows, through `mla_decode` in interpret mode or the XLA gather)
    down the engine's path, in float32, against the reference's plain form
    over the same tokens with the upstream rotary layout.  Only float32
    rounding of another order separates them: 2e-4 on logits of unit
    scale; bf16 anywhere on either side would move them by about 1e-2, and
    a routing flip by O(0.1)."""
    cfg = config(torch_dtype="float32")
    w = make_weights(cfg, 2**31 + 7)
    pc = V2.program_config(cfg).replace(paged_attn_kernel=route)
    params = V2.program_params(w)
    prompts = [np.arange(5, 18, dtype=np.int32),
               np.arange(40, 47, dtype=np.int32)]
    served = program_tests().serve_logits(pc, params, prompts, 4, 20,
                                          page_size=8, max_seq=128)
    for prompt, got in zip(prompts, served):
        toks = list(prompt) + [int(np.argmax(g)) for g in got[:-1]]
        row = np.asarray(toks, np.int32)[None]
        with jax.default_matmul_precision("highest"):
            x, _ = V2.hidden(cfg, w, row, np.zeros_like(row), quant=False)
            ref = np.asarray(V2.logits(cfg, w, x[0], quant=False))
        want = ref[len(prompt) - 1:]
        np.testing.assert_allclose(np.stack(got), want, rtol=2e-4,
                                   atol=2e-4)


def test_program_yarn_is_the_references():
    """The program's YaRN tables and softmax scale (models/mla.py) and the
    reference's, computed apart, agree at the published widths."""
    from repro.models import mla
    pc = V2.program_config(REAL)
    inv, msc = mla.rope_tables(pc)
    r_inv, r_msc, r_scale = V2.yarn(REAL)
    np.testing.assert_allclose(np.asarray(inv), np.asarray(r_inv),
                               rtol=1e-6)
    assert msc == pytest.approx(r_msc) and r_msc == pytest.approx(1.0)
    assert mla.softmax_scale(pc) == pytest.approx(r_scale)
    assert r_scale == pytest.approx(192 ** -0.5 * (
        0.1 * 0.707 * math.log(40) + 1) ** 2)


def test_program_config_keeps_every_published_width():
    pc = V2.program_config(REAL)
    assert (pc.n_layers, pc.d_model, pc.n_heads, pc.vocab) == (
        27, 2048, 16, 102400)
    assert (pc.kv_lora_rank, pc.qk_nope_dim, pc.qk_rope_dim,
            pc.v_head_dim) == (512, 128, 64, 128)
    assert (pc.moe_experts, pc.moe_held, pc.moe_topk, pc.moe_shared,
            pc.moe_d_ff, pc.d_ff, pc.n_dense_layers) == (
        64, 8, 6, 2, 1408, 10944, 1)
    assert (pc.rope_factor, pc.rope_original_max) == (40.0, 4096)
    assert REAL["reduced"] == ["n_routed_experts"]


def test_control_is_not_the_reference():
    from chipbench import reference
    cfg = config()
    w = make_weights(cfg, 2**31 + 9)
    rng = np.random.default_rng(0)
    rows = [(rng.integers(0, 4096, 32, dtype=np.int32),
             rng.integers(0, 4096, 40).tolist()) for _ in range(2)]
    got = reference.compare(cfg, w, rows, n_rows=2, control=True)
    assert got["tokens"] == 80
    assert got["control"]["not_first"] > 0
    assert got["control"]["mean_gap"] > 0


# --------------------------------------------------------------------------
# a tiny cell of it, come in by new files alone


def snapshot(root):
    import hashlib
    return {p.relative_to(root).as_posix(): hashlib.sha256(
        p.read_bytes()).hexdigest() for p in root.rglob("*") if p.is_file()}


def test_new_model_comes_in_by_new_files_alone(tmp_path, capsys):
    """The model module, its configuration, the long-decode mix and the
    latent attention readers are found by name: a tiny cell of the model
    runs and is correct with nothing but its configuration file and the
    entries for its cell added."""
    with tiny.cache_settings_kept():
        root = tiny.make_root(tmp_path)
        before = snapshot(root)
        for name in ("models/deepseek_v2.py", "traffic/long_decode_p512.json",
                     "metrics/mla_decode_roofline.py",
                     "metrics/mla_decode_ms.batch.py"):
            assert "chipbench/" + name in before
        add_cell(root)
        after = snapshot(root)
        assert sorted(set(after) - set(before)) == [
            "chipbench/configs/tiny-mla.json"]
        c = bench.resolve(CELL, root)
        assert c.model.__file__.endswith("deepseek_v2.py")
        assert {"mla_decode_roofline", "mla_decode_ms.batch"} <= {
            m["name"] for m in c.per_layer}
        rc = run.main(["--workload", CELL, "--seed", str(2**31 + 5),
                       "--seconds", "2", "--trace", "0"],
                      require_chip=False, root=root)
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True, result["check"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"out_tok_s", "setup_s"} <= set(result["metrics"])


def test_the_cell_resolves_to_the_long_decode_mix():
    c = bench.resolve(REAL_CELL)
    assert c.traffic["prompt"] == {"dist": "fixed", "value": 512}
    out = c.traffic["output"]
    assert (out["median"], out["min"], out["max"]) == (1536, 768, 2559)
    assert 512 + out["max"] < c.config["serving"]["max_seq"]
    assert c.traffic["stratum"] == c.config["serving"]["n_slots"] == 64


# --------------------------------------------------------------------------
# kv_rows on the dispatch spans


def driven(root, cell, n=6, seed=2**31 + 11):
    c = bench.resolve(cell, root)
    w = make_weights(c.config, seed)
    eng = run.build_engine(c.config, w, 3)
    gen = traffic.Traffic(c.traffic, seed, c.config["vocab_size"])
    drv = drivers.Driver(eng, gen, lambda s: Request(
        uid=s.uid, prompt=s.prompt, max_new=s.max_new))
    for spec in gen.take(n):
        drv.submit(spec, drivers.CLOCK())
    while drv.busy():
        drv.step()
    return c, drv


@pytest.mark.parametrize("cell", [CELL, "tiny.tiny_batch"])
def test_dispatch_kv_rows_are_the_harness_depth_sums(root, cell):
    """Each step's dispatch counts the keys its lanes read at their true
    depths: the same sum the driver records for the tokens that the step
    made visible (`StepRecord.depth_sum`), step by step, for a latent and
    a K/V model."""
    _, drv = driven(root, cell)
    spans = drv.engine.telemetry.spans(name="repro.engine.dispatch")
    rows = [sum(s.attrs["kv_rows"] for s in spans
                if r.start <= s.t0 and s.t1 <= r.end) for r in drv.steps]
    assert sum(rows) > 0
    assert rows == [r.depth_sum for r in drv.steps]


# --------------------------------------------------------------------------
# work counts and the readers of the latent attention kernel


def test_work_counts_of_the_cell():
    cfg = REAL
    d, H, fe = 2048, 16, 1408
    attn = d * H * 192 + d * 576 + H * 128 * 512 + H * 512 * 128 + H * 128 * d
    moe = d * 64 + 3 * d * fe * (2 + 6 * 8 / 64)
    want = 2 * (27 * attn + 3 * d * 10944 + 26 * moe + d * 102400)
    assert V2.weight_flops_per_token(cfg) == pytest.approx(want)
    assert V2.held_rows_per_token(cfg) == 0.75
    assert V2.attn_flops(cfg, 10) == 27 * 2 * 16 * (576 + 512) * 10
    # 1152 bytes a key and layer, plus each lane's query and output
    assert V2.attn_bytes(cfg, 2, 100) == 27 * (1152 * 100 + 2 * 2 * 16 * (
        576 + 512))
    assert V2.moe_flops(cfg, 10) == 6 * d * fe * 10
    assert V2.moe_bytes(cfg, 10, 3) == 2 * (3 * 3 * d * fe + 2 * d * 10)
    assert V2.drs_flops(cfg, 5) == 0.0
    # the step's least bytes at 64 lanes and a mean depth of 1440: latent
    # rows, the held experts and the rest of the weights, about 10.6 ms
    rows = V2.attn_bytes(cfg, 64, 64 * 1440)
    assert rows / 819e9 * 1e3 == pytest.approx(3.5, abs=0.1)


class Trace:
    """A device trace that holds `seconds` of the kernel, or none."""

    def __init__(self, seconds):
        self.seconds = seconds

    def ops(self, *patterns):
        assert patterns == (r"^mla_decode(\.\d+)?$",)
        return (self.seconds, 3) if self.seconds else (0.0, 0)


def ctx_for(cell, drv, seconds):
    return report.Ctx(cfg=cell.config, mix=cell.traffic,
                      peaks={"bf16_flops_per_s": 197e12,
                             "hbm_bytes_per_s": 819e9},
                      setup_s=0.0,
                      window=(drv.steps[0].start, drv.steps[-1].end),
                      seen=drv.everyone(), steps=drv.steps,
                      trace=Trace(seconds), traced_steps=drv.steps)


def test_readers_read_the_counted_work(root):
    cell, drv = driven(root, CELL)
    spans = drv.engine.telemetry.spans()
    rows = sum(s.attrs["kv_rows"] for s in spans
               if s.name == "repro.engine.dispatch")
    lanes = sum(s.attrs["lanes"] for s in spans
                if s.name == "repro.engine.step")
    assert rows and lanes
    least = max(V2.attn_flops(cell.config, rows) / 197e12,
                V2.attn_bytes(cell.config, lanes, rows) / 819e9)
    roof = cell.reader({"name": "mla_decode_roofline"})
    assert roof(ctx_for(cell, drv, 2 * least)) == pytest.approx(50.0)
    ms = cell.reader({"name": "mla_decode_ms.batch"})
    decode = sum(1 for r in drv.steps if r.lanes)
    assert ms(ctx_for(cell, drv, 0.3)) == pytest.approx(300.0 / decode)


@pytest.mark.parametrize("name", ["mla_decode_roofline",
                                  "mla_decode_ms.batch"])
def test_readers_read_nothing_without_the_kernel(root, name):
    """A K/V model's trace holds no `mla_decode`: None, never an error;
    nor without the counts (a program that records no `kv_rows`)."""
    cell, drv = driven(root, "tiny.tiny_batch", n=2)
    read = cell.reader({"name": name})
    assert read(ctx_for(cell, drv, 0.0)) is None
    if name == "mla_decode_roofline":
        for rec in drv.engine.telemetry.spans(name="repro.engine.dispatch"):
            rec.attrs.pop("kv_rows")
        assert read(ctx_for(cell, drv, 0.1)) is None
