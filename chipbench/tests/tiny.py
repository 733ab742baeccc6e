"""A test-size copy of the benchmark: the chipbench files with tiny
configurations and mixes, in a directory of its own, for runs on the CPU."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parent
PKG = HERE.parent
REPO = PKG.parent
DATA = HERE / "data"

CELLS = {"tiny.tiny_batch": ("tiny", "tiny_batch"),
         "tiny-dsg.tiny_batch": ("tiny-dsg", "tiny_batch"),
         "tiny.tiny_chat": ("tiny", "tiny_chat")}


def make_root(tmp: Path) -> Path:
    """A checkout-like directory holding chipbench and a BENCHMARK.json
    whose cells are the tiny ones; every metric applies where its
    real-size twin does (batch or chat, dense or DSG)."""
    root = Path(tmp) / "root"
    shutil.copytree(PKG, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name in ("tiny", "tiny-dsg"):
        shutil.copy(DATA / f"{name}.json",
                    root / "chipbench" / "configs" / f"{name}.json")
    for mix in ("tiny_batch", "tiny_chat"):
        shutil.copy(DATA / f"{mix}.json",
                    root / "chipbench" / "traffic" / f"{mix}.json")
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    bench = dict(real)
    bench["configs"] = [
        {"name": n, "source": "test", "file": f"chipbench/configs/{n}.json",
         "reduced": [], "why": "test size"} for n in ("tiny", "tiny-dsg")]
    bench["workloads"] = [
        {"name": c, "config": cfg, "traffic": mix, "chips": 1, "why": "test"}
        for c, (cfg, mix) in CELLS.items()]

    def remap(names):
        out = set()
        for n in names:
            cfg, mix = n.rsplit(".", 1)
            dsg = cfg.endswith("dsg50")
            for c, (tc, tm) in CELLS.items():
                if tc.endswith("dsg") == dsg and tm.endswith(
                        "batch" if "batch" in mix else "chat"):
                    out.add(c)
        return sorted(out)

    for key in ("end_to_end", "per_layer"):
        bench[key] = [dict(m, workloads=remap(m["workloads"]))
                      if "workloads" in m else dict(m) for m in real[key]]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root
