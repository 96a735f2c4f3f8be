"""Turn a cell's name into its files.

``BENCHMARK.json`` at the root names each cell's configuration and traffic
mix; everything that belongs to one of them sits in a file of its own that
is found by name:

* ``bench/configs/<config>.json``  the model's published sizes (Hugging Face
  ``config.json`` keys), ``source``, ``reduced``, ``assumed`` and the
  deployment it stands for;
* ``bench/traffic/<mix>.json``     the mix: loop, lengths, clients or rate,
  the engine's settings, and the size of the correctness sample;
* ``bench/limits/<cell>.json``     the cell's correctness limits, by the
  name of the number each holds (PERF.md gives the readings behind them);
* ``bench/metrics/<metric>.py``    one per-layer metric's reader;
* ``bench/models/<model_type>.py`` one model type: the configuration's
  ``model_type`` names it (``load_model``).

A later cell, mix, configuration, metric or model type is new files and
new entries; no file here changes.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import sys
import zlib
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list       # metric entries this cell reports, trace 0
    per_layer: list        # metric entries this cell reports, trace 1


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    return json.loads(path.read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    config = _json(root / cfgs[w["config"]]["file"])
    traffic = _json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    limits = _json(root / "bench" / "limits" / f"{name}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def load_reader(metric: str, root: Path = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {metric!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@functools.lru_cache(maxsize=None)
def _module(path: str):
    """The module at ``path``, executed once per process, under a name of
    its own in ``sys.modules`` (dataclasses look their module up there)."""
    name = f"bench_model_{Path(path).stem}_{zlib.crc32(path.encode()):08x}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_model(config: dict, root: Path = ROOT):
    """The module ``bench/models/<model_type>.py`` of a configuration: the
    model type's mapping onto the program (``model_config``), the weight
    laws of its layers (``leaf_law``), its float32 reference layer
    (``layer_weights``, ``layer``) and its operation counts (``cost``)."""
    path = root / "bench" / "models" / f"{config.get('model_type')}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"model_type {config.get('model_type')!r} has no module: {path}")
    return _module(str(path))
