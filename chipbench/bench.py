"""Resolves a cell of `BENCHMARK.json` to its files, by name.

A configuration is the JSON file its entry names; a traffic mix is
`chipbench/traffic/<traffic>.json`; a per-layer metric is the reader
`chipbench/metrics/<metric>.py`, which defines `read(ctx) -> float | None`.
Adding any of them takes new files and new entries, never an edit.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)
    root: Path = ROOT

    def reader(self, metric: dict):
        """The `read` function of a per-layer metric's reader file."""
        path = self.root / "chipbench" / "metrics" / f"{metric['name']}.py"
        spec = importlib.util.spec_from_file_location(
            "chipbench_metric_" + metric["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def load(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: Path = ROOT, bench: dict = None) -> Cell:
    bench = bench if bench is not None else load(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads((root / "chipbench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
                root=root)
