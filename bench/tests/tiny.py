"""A cell small enough for the CPU: the Qwen3 layout at toy widths."""
from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness.spec import Cell  # noqa: E402

CONFIG = {
    "name": "qwen3-tiny", "model_type": "qwen3", "attention_bias": False,
    "head_dim": 16, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "tie_word_embeddings": True, "vocab_size": 256,
    "deployment": {"tensor_parallel": 1},
}

MIX = {
    "loop": "closed", "clients": 2, "think_s": 0.0,
    "prompt_len": {"dist": "uniform", "min": 20, "max": 40},
    "output_len": {"dist": "uniform", "min": 6, "max": 12},
    "strata": 8,
    "engine": {"slots": 2, "capacity": 64, "kv_layout": "dense",
               "max_admit": 2},
    "check": {"min_tokens": 16},
}


# The tiny cells' limit on the gap the real cells compare, set from their
# own CPU readings: sound runs read 0 to 9.8e-4 (twelve seeds of the fault
# tests' cell, four of the four-device chat-shaped cell, three of the
# four-device exchange cell), the planted faults 0.117 to 2.71 (three
# seeds each).
LIMIT = 0.01


def cell(tp: int = 1, tied: bool = True) -> Cell:
    config = dict(CONFIG, tie_word_embeddings=tied,
                  deployment={"tensor_parallel": tp})
    return Cell(name="tiny", chips=tp, config=config, traffic=dict(MIX),
                limits={"mean_logit_gap": LIMIT}, end_to_end=[
                    {"name": n, "unit": u} for n, u in (
                        ("output_tokens_per_s", "tokens/s"),
                        ("itl_p99_s", "s"), ("setup_s", "s"))],
                per_layer=[])
