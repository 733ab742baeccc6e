"""The DeepSeekMoE model module (`chipbench/models/deepseek.py`) at test size
on the CPU: the shares of an expert-parallel deployment add up to the uncut
layer, the engine's prefill-then-decode logits agree with the reference, a
whole run of a tiny cell of it is correct, its work counts, and the readers
of the expert layer's kernel.  The chip readings its limit was set from are
held against it by `test_chipbench_check.test_chip_readings_against_the_limits`
(conftest.py)."""
import json
import shutil

import jax
import numpy as np
import pytest

from chipbench import bench, drivers, report, run, traffic
from chipbench.tests import tiny
from chipbench.weights import make_weights
from repro.serving.scheduler import Request

TINY = json.loads((tiny.DATA / "tiny-moe.json").read_text())
CELL = "tiny-moe.tiny_batch"
ds = bench.load_model("deepseek")


def config(**kw):
    return dict(TINY, **kw)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A tiny checkout with a cell of the tiny MoE configuration, in the
    workloads of every metric the real MoE cell reports."""
    with tiny.cache_settings_kept():
        root = tiny.make_root(tmp_path_factory.mktemp("tiny"))
        shutil.copy(tiny.DATA / "tiny-moe.json",
                    root / "chipbench" / "configs" / "tiny-moe.json")
        b = json.loads((root / "BENCHMARK.json").read_text())
        b["configs"].append({"name": "tiny-moe", "source": "test",
                             "file": "chipbench/configs/tiny-moe.json",
                             "reduced": ["n_routed_experts"], "why": "test"})
        b["workloads"].append({"name": CELL, "config": "tiny-moe",
                               "traffic": "tiny_batch", "chips": 1,
                               "why": "test"})
        for m in b["end_to_end"] + b["per_layer"]:
            if "tiny.tiny_batch" in m.get("workloads", ()):
                m["workloads"].append(CELL)
        (root / "BENCHMARK.json").write_text(json.dumps(b))
        yield root


# --------------------------------------------------------------------------
# the shares


def test_shares_add_up_to_the_uncut_reference_layer():
    """Four chips of four experts each (16 published): at one seed every
    share makes the same weights but its own experts, and the routed parts
    of all four, with the shared experts and attention counted once, are
    the layer that holds all sixteen."""
    seed = 2**31 + 3
    full_cfg = config(n_routed_experts=16, expert_offset=0)
    full = make_weights(full_cfg, seed)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 128))
    key = ds._cfg_key(full_cfg)
    want = ds._moe_layer(x, full["layers"], 0, cfg_key=key, quant=False)
    without = dict(full["layers"], moe=dict(full["layers"]["moe"],
                   w_down=full["layers"]["moe"]["w_down"] * 0))
    base = ds._moe_layer(x, without, 0, cfg_key=key, quant=False)
    got = base
    for c in range(4):
        cfg = config(expert_offset=4 * c)
        w = make_weights(cfg, seed)
        for k in ("w_gate", "w_up", "w_down"):    # its experts of the 16
            np.testing.assert_array_equal(
                np.asarray(w["layers"]["moe"][k]),
                np.asarray(full["layers"]["moe"][k][:, 4 * c:4 * c + 4]))
        got = got + ds._moe_layer(x, w["layers"], 0, cfg_key=ds._cfg_key(cfg),
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


def test_engine_logits_match_the_reference():
    """Prefill then decode down the engine's paged path, in float32, against
    the reference's full forward over the same tokens.  The program runs
    the same mathematics in another order (grouped rows, a cache, blocked
    attention) at default precision on the CPU, so only float32 rounding
    separates them: 2e-4 on logits of unit scale.  A routing flip (a near
    tie between the 6th and 7th expert) would show as an O(0.1) gap."""
    cfg = config(torch_dtype="float32")
    w = make_weights(cfg, 2**31 + 7)
    pc = ds.program_config(cfg)
    params = ds.program_params(w)
    prompts = [np.arange(5, 18, dtype=np.int32),
               np.arange(40, 47, dtype=np.int32)]
    served = program_tests().serve_logits(pc, params, prompts, 4, 6,
                                          page_size=16, max_seq=128)
    for prompt, got in zip(prompts, served):
        toks = list(prompt) + [int(np.argmax(g)) for g in got[:-1]]
        row = np.asarray(toks, np.int32)[None]
        with jax.default_matmul_precision("highest"):
            x, _ = ds.hidden(cfg, w, row, np.zeros_like(row), quant=False)
            ref = np.asarray(ds.logits(cfg, w, x[0], quant=False))
        want = ref[len(prompt) - 1:]
        np.testing.assert_allclose(np.stack(got), want, rtol=2e-4,
                                   atol=2e-4)


def test_sound_run_is_correct(root, capsys):
    with tiny.cache_settings_kept():
        rc = run.main(["--workload", CELL, "--seed", str(2**31 + 5),
                       "--seconds", "2", "--trace", "0"],
                      require_chip=False, root=root)
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True, result["check"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"out_tok_s", "setup_s"} <= set(result["metrics"])


def test_control_is_not_the_reference():
    """The control (int8 weight products, the router's among them) puts
    another token first at some positions, where the reference reads no
    gap.  At this size int8 and bf16 rounding are alike; the control is
    held against the limit on the chip, at the cell's own size (PERF.md)."""
    from chipbench import reference
    cfg = config()
    w = make_weights(cfg, 2**31 + 9)
    rng = np.random.default_rng(0)
    rows = [(rng.integers(0, 4096, 32, dtype=np.int32),
             rng.integers(0, 4096, 40).tolist()) for _ in range(2)]
    got = reference.compare(cfg, w, rows, n_rows=2, control=True)
    assert got["tokens"] == 80
    assert got["program"]["mean_gap"] > 0           # random tokens served
    assert got["control"]["not_first"] > 0
    assert got["control"]["mean_gap"] > 0


def test_a_program_that_cannot_serve_it_stops_before_any_weight(
        monkeypatch):
    """The parent of this configuration's program has no held experts:
    `make_weights` builds the program configuration first, so such a run
    fails at once instead of making 6.6 GB of weights."""
    def refuse(cfg):
        raise TypeError("no such ModelConfig field")

    monkeypatch.setattr(ds, "program_config", refuse)
    made = []
    monkeypatch.setattr(jax, "jit", lambda *a, **k: made.append(a))
    with pytest.raises(TypeError):
        ds.make_weights(config(), 1)
    assert not made


# --------------------------------------------------------------------------
# work counts


def test_work_counts_of_the_cell():
    cfg = json.loads((bench.ROOT / "chipbench" / "configs"
                      / "deepseek-moe-16b-ep8.json").read_text())
    d, fe = 2048, 1408
    attn = 2048 * 48 * 128 + 16 * 128 * 2048
    moe = 2048 * 64 + 3 * d * fe * (2 + 6 * 8 / 64)
    want = 2 * (28 * attn + 3 * d * 10944 + 27 * moe + d * 102400)
    assert ds.weight_flops_per_token(cfg) == pytest.approx(want)
    assert ds.held_rows_per_token(cfg) == 0.75
    assert ds.moe_flops(cfg, 10) == 6 * d * fe * 10
    assert ds.moe_bytes(cfg, 10, 3) == 2 * (3 * 3 * d * fe + 2 * d * 10)
    assert ds.attn_bytes(cfg, 2, 100) == 28 * 2 * (2 * 16 * 128 * 100
                                                   + 2 * 4 * 16 * 128)
    assert ds.drs_flops(cfg, 5) == 0.0


# --------------------------------------------------------------------------
# the readers of the expert layer


class Trace:
    """A device trace that holds `seconds` of the kernel, or none."""

    def __init__(self, seconds):
        self.seconds = seconds

    def ops(self, *patterns):
        assert patterns == (r"^moe_experts(\.\d+)?$",)
        return (self.seconds, 3) if self.seconds else (0.0, 0)


def driven(root, cell, n=4, seed=2**31 + 11):
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
    spans = [s for s in drv.engine.telemetry.spans()
             if s.name in ("repro.engine.step", "repro.engine.admit")]
    rows = sum(s.attrs["moe_rows"] for s in spans)
    hit = sum(s.attrs["moe_experts_hit"] for s in spans)
    assert rows and hit
    least = max(ds.moe_flops(cell.config, rows) / 197e12,
                ds.moe_bytes(cell.config, rows, hit) / 819e9)
    roof = cell.reader({"name": "moe_experts_roofline"})
    assert roof(ctx_for(cell, drv, 2 * least)) == pytest.approx(50.0)
    ms = cell.reader({"name": "moe_experts_ms.batch"})
    decode = sum(1 for r in drv.steps if r.lanes)
    assert ms(ctx_for(cell, drv, 0.3)) == pytest.approx(300.0 / decode)


@pytest.mark.parametrize("name", ["moe_experts_roofline",
                                  "moe_experts_ms.batch"])
def test_readers_read_nothing_without_the_kernel(root, name):
    """A dense run's trace holds no `moe_experts`, nor its spans the
    counts: None, never an error."""
    cell, drv = driven(root, "tiny.tiny_batch", n=2)
    read = cell.reader({"name": name})
    assert read(ctx_for(cell, drv, 0.0)) is None
    if name == "moe_experts_roofline":
        assert read(ctx_for(cell, drv, 0.1)) is None     # no counts

