"""Everything the harness knows about a cell, found by name.

* ``BENCHMARK.json`` at the root of the checkout: cells, configurations
  and metrics;
* ``bench/configs/<config>.json``: the configuration as it is run (the
  file that ``BENCHMARK.json`` names);
* ``bench/traffic/<traffic>.json``: the cell's grid, scale and call shape;
* ``bench/limits/<cell>.json``: the limit of each number that decides
  ``correct``;
* ``bench/checks/<check>.py``: the comparison with the plain reference
  that the traffic names;
* ``bench/models/<model>.py``: what the benchmark knows of the model that
  a configuration's ``"model"`` names (``model``);
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(ctx)``.

A later cell, configuration or metric is a new file here, not an edit.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def _named(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; have "
                   f"{[e['name'] for e in entries]}")


def load_module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model(cfg: dict) -> ModuleType:
    """The file of the configuration's model, ``bench/models/<model>.py``.
    It holds ``macs(cfg)`` (forward multiply-adds of one sample and of its
    first layer, for ``bench.flops``), the plain reference's ``init(cfg,
    key, dtype)`` and ``apply(cfg, params, inputs)``, and, where the
    grid's call needs them, ``scale(cfg)`` (fields of the program's
    ``BenchScale``) and ``sweep_kwargs(cfg)`` (further keyword arguments
    of ``run_sweep_cells``)."""
    path = BENCH / "models" / f"{cfg['model']}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"configuration {cfg.get('name')!r} names model {cfg['model']!r}, "
            f"which has no file {path}")
    return load_module(path)


class Cell:
    """One workload of ``BENCHMARK.json`` with its configuration, traffic,
    limits, check and metric readers."""

    def __init__(self, name: str, chips: int, config: dict, traffic: dict,
                 limits: dict, end_to_end: List[dict], per_layer: List[dict]):
        self.name, self.chips = name, int(chips)
        self.config, self.traffic, self.limits = config, traffic, limits
        self.check = load_module(BENCH / "checks" / f"{traffic['check']}.py")
        self.end_to_end = self._metrics(end_to_end)
        self.per_layer = self._metrics(per_layer)

    @classmethod
    def load(cls, name: str, root: Path = ROOT) -> "Cell":
        bench = benchmark(root)
        entry = _named(bench["workloads"], name, "workload")
        cfg_entry = _named(bench["configs"], entry["config"], "configuration")
        return cls(name, entry["chips"], _json(root / cfg_entry["file"]),
                   _json(BENCH / "traffic" / f"{entry['traffic']}.json"),
                   _json(BENCH / "limits" / f"{name}.json"),
                   bench["end_to_end"], bench["per_layer"])

    def _metrics(self, entries: List[dict]) -> List[dict]:
        return [m for m in entries
                if "workloads" not in m or self.name in m["workloads"]]

    def readers(self, trace: bool) -> Dict[str, ModuleType]:
        """name -> reader module of the metrics this run reports."""
        return {m["name"]: load_module(BENCH / "metrics" / f"{m['name']}.py")
                for m in (self.per_layer if trace else self.end_to_end)}

    def units(self, trace: bool) -> Dict[str, str]:
        return {m["name"]: m["unit"]
                for m in (self.per_layer if trace else self.end_to_end)}
