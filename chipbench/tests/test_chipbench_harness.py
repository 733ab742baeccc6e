"""BENCHMARK.json against its files and its contract, and the data-driven
layout: a new configuration, mix or metric is found by name."""
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import bench
from chipbench.tests import tiny

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = 24                      # what later PRs may grow to
    total = ((2 + 14 * cells) * (BENCH["run_seconds"] + 60)
             + cells * 2 * 90 + 1200)
    assert total <= 43200
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_entry_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("chipbench/")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names)) and "setup_s" in names


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = bench.resolve(cell)
    assert c.config["arch"] and c.traffic["driver"] in ("backlog",
                                                        "open_loop")
    assert c.model is bench.model(c.config)
    assert c.model.__file__.endswith(f"{c.config['model_type']}.py")
    e2e = [m["name"] for m in c.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(c.reader(m))
    for m in c.per_layer:
        assert m["moves"] in e2e            # it moves what the cell reports
    rooflines = [m for m in c.per_layer if m["name"].endswith("_roofline")]
    for r in rooflines:
        assert any("mfu" in m["name"] and m["moves"] == r["moves"]
                   for m in c.per_layer)


def snapshot(root: Path) -> dict:
    return {p.relative_to(root).as_posix(): hashlib.sha256(
        p.read_bytes()).hexdigest()
        for p in root.rglob("*") if p.is_file()}


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = tiny.make_root(tmp_path)
    before = snapshot(root)
    pkg = root / "chipbench"
    cfg = json.loads((pkg / "configs" / "tiny.json").read_text())
    (pkg / "configs" / "tiny-wide.json").write_text(
        json.dumps(dict(cfg, hidden_size=128)))
    mix = json.loads((pkg / "traffic" / "tiny_batch.json").read_text())
    (pkg / "traffic" / "tiny_long.json").write_text(
        json.dumps(dict(mix, output=dict(mix["output"], max=96))))
    (pkg / "metrics" / "steps_in_window.py").write_text(
        "def read(ctx):\n    return float(len(ctx.steps))\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny-wide", "source": "test",
                         "file": "chipbench/configs/tiny-wide.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "tiny-wide.tiny_long",
                           "config": "tiny-wide", "traffic": "tiny_long",
                           "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                           "better": "higher", "source": "program_counter",
                           "layer": "engine", "moves": "out_tok_s",
                           "workloads": ["tiny-wide.tiny_long"]})
    b["end_to_end"] = [dict(m, workloads=m["workloads"]
                            + ["tiny-wide.tiny_long"])
                       if m["name"] == "out_tok_s" else m
                       for m in b["end_to_end"]]
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    cell = bench.resolve("tiny-wide.tiny_long", root)
    assert cell.config["hidden_size"] == 128
    assert cell.traffic["output"]["max"] == 96
    names = [m["name"] for m in cell.per_layer]
    assert names == ["steps_in_window"]
    reader = cell.reader(cell.per_layer[0])
    assert reader(type("Ctx", (), {"steps": [1, 2, 3]})()) == 3.0
    after = snapshot(root)
    changed = [p for p in before if before[p] != after.get(p)]
    assert changed == ["BENCHMARK.json"]     # every other file as it was


def run_script(cwd: Path, env: dict):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_an_accelerator():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = run_script(REPO, env)
    assert out.returncode == 3, out.stderr[-2000:]
    assert '"correct"' not in out.stdout
    assert "needs an accelerator" in out.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    """A directory holding BENCHMARK.json and chipbench alone has no system
    to run: the run exits non-zero and prints no result."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    code = ("import sys; sys.path.insert(0, '.'); from chipbench import run; "
            f"sys.exit(run.main(['--workload', {CELLS[0]!r}, '--seed', '1', "
            "'--seconds', '1'], require_chip=False))")
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "repro" in out.stderr


#: A second model, as a later change would bring it: an InternLM2 block
#: whose output head is its input embedding, so its weights have no
#: `lm_head`, the program serves it with `tie_embeddings`, and its reference
#: reads the head from the embedding.  The broken variant serves the program
#: another head (each token's row is its neighbour's), which the check sees.
TIED_MODEL = '''"""A tied-embedding decoder with InternLM2's block."""
from functools import partial

import jax
import jax.numpy as jnp

from chipbench import bench
from chipbench.reference import mm, rms_norm

BROKEN = {broken}
base = bench.load_model("internlm2")
hidden, dsg_groups = base.hidden, base.dsg_groups
weight_flops_per_token, drs_flops = base.weight_flops_per_token, base.drs_flops
attn_flops, attn_bytes = base.attn_flops, base.attn_bytes
ffn_csr_flops, ffn_csr_bytes = base.ffn_csr_flops, base.ffn_csr_bytes


def program_config(cfg):
    return base.program_config(cfg).replace(tie_embeddings=not BROKEN)


def make_weights(cfg, seed):
    w = base.make_weights(cfg, seed)
    del w["lm_head"]
    return w


def program_params(w):
    params = base.program_params(w)
    if BROKEN:
        params["lm_head"] = jnp.roll(w["embed"], 1, axis=0).T
    return params


@partial(jax.jit, static_argnames=("eps", "quant"))
def _logits(x, ln_final, embed, eps, quant):
    h = rms_norm(x, ln_final.astype(jnp.float32), eps)
    return mm("td,vd->tv", h, embed.astype(jnp.float32), (1,), quant)


def logits(cfg, w, x, quant):
    return _logits(x, w["ln_final"]["scale"], w["embed"],
                   eps=cfg["rms_norm_eps"], quant=quant)
'''


def add_config(root: Path, name: str, model_type, traffic="tiny_batch"):
    """A configuration of `model_type` (None: none named) and one cell of it
    that reports `out_tok_s`, as new files and new entries."""
    pkg = root / "chipbench"
    cfg = json.loads((pkg / "configs" / "tiny.json").read_text())
    cfg.pop("model_type")
    if model_type is not None:
        cfg.update(model_type=model_type, tie_word_embeddings=True)
    (pkg / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    cell = f"{name}.{traffic}"
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": name, "source": "test",
                         "file": f"chipbench/configs/{name}.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": cell, "config": name, "traffic": traffic,
                           "chips": 1, "why": "test"})
    b["end_to_end"] = [dict(m, workloads=m["workloads"] + [cell])
                       if m["name"] == "out_tok_s" else m
                       for m in b["end_to_end"]]
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return cell


def run_in_process(root: Path, cell: str, capsys):
    from chipbench import run
    with tiny.cache_settings_kept():
        rc = run.main(["--workload", cell, "--seed", str(2**31 + 21),
                       "--seconds", "2", "--trace", "0"],
                      require_chip=False, root=root)
    return rc, capsys.readouterr()


def run_in_root(root: Path, cell: str):
    """A run of `cell` on the CPU in a process of its own, from `root`, so
    that its `chipbench` and model modules are the root's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"))
    code = ("import sys; sys.path.insert(0, '.'); from chipbench import run; "
            f"sys.exit(run.main(['--workload', {cell!r}, '--seed', "
            f"'{2**31 + 21}', '--seconds', '2', '--trace', '0'], "
            "require_chip=False))")
    return subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("broken", [False, True])
def test_new_model_comes_in_by_new_files_alone(tmp_path, broken):
    """A configuration of another `model_type` is served and checked on
    the CPU with nothing but a model module, a configuration file and
    entries for its cell; a variant whose program is served a head other
    than the one its reference reads comes out not correct."""
    root = tiny.make_root(tmp_path)
    before = snapshot(root)
    model = "tied_decoder_broken" if broken else "tied_decoder"
    (root / "chipbench" / "models" / f"{model}.py").write_text(
        TIED_MODEL.replace("{broken}", str(broken)))
    cell = add_config(root, "tied", model)
    after = snapshot(root)
    assert sorted(set(after) - set(before)) == [
        "chipbench/configs/tied.json", f"chipbench/models/{model}.py"]
    assert [p for p in before if before[p] != after[p]] == ["BENCHMARK.json"]

    assert bench.resolve(cell, root).model.BROKEN is broken
    out = run_in_root(root, cell)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["attempted"] > 0 and "out_tok_s" in result["metrics"]
    assert result["correct"] is not broken, result["check"]


@pytest.mark.parametrize("model_type", ["no_such_model", None])
def test_unknown_model_fails_loudly(tmp_path, capsys, model_type):
    """A configuration whose `model_type` has no module, or that names none,
    is never served as another model: the run exits non-zero and prints no
    result."""
    root = tiny.make_root(tmp_path)
    cell = add_config(root, "stray", model_type)
    with pytest.raises(bench.UnknownModel):
        bench.resolve(cell, root)
    rc, out = run_in_process(root, cell, capsys)
    assert rc != 0
    assert '"correct"' not in out.out
    assert "model_type" in out.err
