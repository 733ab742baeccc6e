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
