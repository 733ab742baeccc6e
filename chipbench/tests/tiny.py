"""A test-size copy of the benchmark: the chipbench files with tiny
configurations and mixes, in a directory of its own, for runs on the CPU."""
from __future__ import annotations

import contextlib
import json
import os
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parent
PKG = HERE.parent
REPO = PKG.parent
DATA = HERE / "data"

#: JAX settings that `run.enable_cache` changes
CACHE_KEYS = ("jax_compilation_cache_dir",
              "jax_persistent_cache_min_compile_time_secs",
              "jax_persistent_cache_min_entry_size_bytes")

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


@contextlib.contextmanager
def cache_settings_kept():
    """Puts JAX's compilation-cache settings back after in-process runs,
    which point them into their checkout."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    saved = {k: getattr(jax.config, k) for k in CACHE_KEYS}
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        if env is None:
            os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = env
        compilation_cache.reset_cache()
