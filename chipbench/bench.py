"""Resolves a cell of `BENCHMARK.json` to its files, by name.

A configuration is the JSON file its entry names; a traffic mix is
`chipbench/traffic/<traffic>.json`; a per-layer metric is the reader
`chipbench/metrics/<metric>.py`, which defines `read(ctx) -> float | None`;
a model is the module `chipbench/models/<model_type>.py`, named by the
configuration's `model_type` as a published `config.json` names it.
Adding any of them takes new files and new entries, never an edit.

Everything that knows an architecture lives in its model module; the rest of
the harness (traffic, drivers, engine set-up, trace, readers, and the sampling
and gap statistics of the check) is the same for every model.  A model module
defines, each taking the configuration file `cfg` as a dict:

- `program_config(cfg)`: the program's ModelConfig that serves it;
- `make_weights(cfg, seed)`: its whole weights tree, made on the device from
  the seed in one jitted call (`weights.key_of`), in the dtype it is served
  in; a DSG configuration's projection is the leaf `r`;
- `program_params(w)`: the engine's params tree from those weights;
- `hidden(cfg, w, tokens, src, quant, given=None, use_given=None)`: the
  plain float32 reference over token rows (B, T) up to the final residual
  stream (B, T, d), with `quant` the int8 control; returns it and, under
  DSG, each DSG layer's group scores (B, T, G) (`reference.py` says what
  `src`, `given` and `use_given` hold);
- `logits(cfg, w, x, quant)`: the reference from one row's residual stream
  x (T, d) to its logits (T, V): final norm and output head, tied or not;
- `dsg_groups(cfg)`: the FFN neuron groups DSG chooses among in a layer;
- the work counts `weight_flops_per_token(cfg)`, `attn_flops(cfg,
  depth_sum)`, `attn_bytes(cfg, lanes, depth_sum)`, `drs_flops(cfg, rows)`,
  `ffn_csr_flops(cfg, lanes)` and `ffn_csr_bytes(cfg, steps, lanes)`, as
  `workcount.py` defines them.

A configuration's model is found through `model(cfg)`: the module of its
`model_type` in this checkout.  A `model_type` with no module is an error
(`UnknownModel`), never read as another model.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass, field
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class UnknownModel(LookupError):
    """A configuration names no `model_type`, or one with no module."""


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_modules = {}      # model module path -> the module


def load_model(model_type: str, root: Path = ROOT):
    """The module `<root>/chipbench/models/<model_type>.py`, loaded once."""
    path = root / "chipbench" / "models" / f"{model_type}.py"
    if not NAME.match(str(model_type)) or not path.is_file():
        known = sorted(p.stem for p in path.parent.glob("*.py"))
        raise UnknownModel(f"no model module for model_type {model_type!r}; "
                           f"known: {known}")
    if path not in _modules:
        _modules[path] = _load(path, "chipbench_model_"
                               + re.sub(r"\W", "_", model_type))
    return _modules[path]


def model(cfg: dict):
    """The model module of a configuration, by its `model_type`, from this
    checkout."""
    if "model_type" not in cfg:
        raise UnknownModel("the configuration names no model_type")
    return load_model(cfg["model_type"])


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    model: object = None
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)
    root: Path = ROOT

    def reader(self, metric: dict):
        """The `read` function of a per-layer metric's reader file."""
        path = self.root / "chipbench" / "metrics" / f"{metric['name']}.py"
        return _load(path, "chipbench_metric_"
                     + metric["name"].replace(".", "_")).read


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
    if "model_type" not in config:
        raise UnknownModel(f"{entry['file']} names no model_type")
    traffic = json.loads((root / "chipbench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                model=load_model(config["model_type"], root),
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
                root=root)
