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
* ``bench/metrics/<metric>.py``    one per-layer metric's reader.

A later cell, mix, configuration or metric is new files and new entries;
no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
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


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file's published
    sizes (``model_type`` "qwen3": GQA with per-head RMSNorm on q and k,
    RoPE, SwiGLU, RMSNorm before each sub-layer)."""
    from repro.models.common import LayerGroup, ModelConfig
    if config.get("model_type") != "qwen3":
        raise ValueError(f"model_type {config.get('model_type')!r} has no "
                         f"mapping onto the program's ModelConfig")
    if config.get("hidden_act") != "silu" or config.get("attention_bias"):
        raise ValueError("qwen3 mapping expects silu and no attention bias")
    L = int(config["num_hidden_layers"])
    return ModelConfig(
        name=config["name"], family="dense", num_layers=L,
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        groups=(LayerGroup(("attn",), L),), mlp_act="silu",
        rope_theta=float(config["rope_theta"]), qk_norm=True,
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        attn_mode="heads")
