"""The loader finds a cell's files by name, and the benchmark file keeps
to its contract."""
import json
import shutil

import pytest

import tiny
from harness import spec
from harness.peaks import peaks

ROOT = tiny.BENCH.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_are_found_by_name(name):
    cell = spec.load_cell(name)
    assert cell.config["name"] in name
    assert cell.traffic["engine"]["slots"] >= 1
    assert cell.limits and all(v > 0 for v in cell.limits.values())
    assert {"setup_s", "output_tokens_per_s"} <= {
        m["name"] for m in cell.end_to_end}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.load_reader(m["name"]))
    cfg = spec.load_model(cell.config).model_config(cell.config)
    assert cfg.d_model == cell.config["hidden_size"]
    assert cell.chips == cell.config["deployment"]["tensor_parallel"]


def test_a_new_cell_is_new_files_and_entries(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    mix = json.loads((tmp_path / "bench/traffic/decode-long.json").read_text())
    mix["clients"] = 2
    (tmp_path / "bench/traffic/pairs.json").write_text(json.dumps(mix))
    (tmp_path / "bench/limits/qwen3-4b.pairs.json").write_text(
        json.dumps({"max_logit_gap": 0.1}))
    (tmp_path / "bench/metrics/ticks_seen.py").write_text(
        "def read(ctx):\n    return float(ctx['window'].ticks)\n")
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "qwen3-4b.pairs", "config": "qwen3-4b",
                           "traffic": "pairs", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "ticks_seen", "unit": "ticks",
                           "better": "higher", "source": "host_clock",
                           "layer": "engine (serve/engine.py)",
                           "moves": "output_tokens_per_s",
                           "workloads": ["qwen3-4b.pairs"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.load_cell("qwen3-4b.pairs", root=tmp_path)
    assert cell.traffic["clients"] == 2
    assert cell.limits["max_logit_gap"] == 0.1
    assert [m["name"] for m in cell.per_layer] == ["ticks_seen"]
    read = spec.load_reader("ticks_seen", root=tmp_path)

    class W:
        ticks = 7
    assert read({"window": W()}) == 7.0


def test_unknown_names_are_refused():
    with pytest.raises(KeyError):
        spec.load_cell("no-such.cell")
    with pytest.raises(FileNotFoundError):
        spec.load_reader("no_such_metric")


def test_unknown_model_type_names_its_missing_file():
    with pytest.raises(FileNotFoundError,
                       match=r"'no_such_type' has no module: .*"
                             r"bench/models/no_such_type\.py"):
        spec.load_model({"model_type": "no_such_type"})


def test_unknown_device_kind_is_refused():
    assert peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v9 imaginary")


def test_qwen3_4b_maps_onto_the_programs_own_config():
    from repro.configs import get_config
    config = spec.load_cell("qwen3-4b.decode-long").config
    ours = spec.load_model(config).model_config(config)
    theirs = get_config("qwen3-4b")
    for k in ("num_layers", "d_model", "num_heads", "num_kv_heads",
              "head_dim", "d_ff", "vocab_size", "rope_theta", "qk_norm",
              "tie_embeddings", "mlp_act", "norm_eps", "groups"):
        assert getattr(ours, k) == getattr(theirs, k), k


# parameters as the model cards give them: Qwen3-4B 4.0B, Qwen3-8B 8.2B
PUBLISHED_PARAMS = {"qwen3-4b": 4.02e9, "qwen3-8b": 8.19e9}


@pytest.mark.parametrize("path", sorted((ROOT / "bench/configs").glob(
    "*.json")), ids=lambda p: p.stem)
def test_config_file_maps_through_its_model_type(path):
    """Every configuration file, in a cell or kept for one, is served by
    its model type's module at the published widths."""
    config = json.loads(path.read_text())
    assert config["name"] == path.stem
    model = spec.load_model(config)
    cfg = model.model_config(config)
    assert (cfg.num_layers, cfg.d_model, cfg.vocab_size) == (
        config["num_hidden_layers"], config["hidden_size"],
        config["vocab_size"])
    cost = model.cost(config)
    assert 0 < cost.decode_flops(1) < cost.decode_flops(1024)
    if config["name"] in PUBLISHED_PARAMS:
        embed = config["vocab_size"] * config["hidden_size"] * (
            1 if config["tie_word_embeddings"] else 2)
        norms = config["num_hidden_layers"] * 2 * (
            config["hidden_size"] + config["head_dim"]) + config["hidden_size"]
        count = cost.matmul_params + embed + norms
        assert abs(count / PUBLISHED_PARAMS[config["name"]] - 1) < 0.002


def test_benchmark_file_keeps_to_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= set(CELLS)
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").is_file()
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] == \
            c["reduced"]
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200
