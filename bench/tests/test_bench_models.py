"""A new model type arrives as one file, ``bench/models/<model_type>.py``,
found from the configuration's ``model_type``: in a copy of the benchmark
with that one file added, and no other file changed, a cell of the new
type is built, served and checked through ``run.execute``."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import tiny

ROOT = tiny.BENCH.parent

# Grouped-query attention with no norm on q and k: the program's
# ``qk_norm=False`` path, whose layers hold no ``q_norm``/``k_norm``.
GQA_PLAIN = '''
import functools
import jax
import jax.numpy as jnp
from harness.reference import causal_gqa, mm, rms, rope


def model_config(config):
    from repro.models.common import LayerGroup, ModelConfig
    L = int(config["num_hidden_layers"])
    return ModelConfig(
        name=config["name"], family="dense", num_layers=L,
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"], groups=(LayerGroup(("attn",), L),),
        mlp_act="silu", rope_theta=float(config["rope_theta"]),
        qk_norm=False, norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        attn_mode="heads")


def leaf_law(name, shape):
    last = name.rsplit("/", 1)[-1]
    fan_in = {"wq": shape[-3], "wk": shape[-3], "wv": shape[-3],
              "wi_gate": shape[-2], "wi_up": shape[-2]}.get(last)
    if fan_in is None and last == "wo":
        fan_in = shape[-3] * shape[-2] if len(shape) == 4 else shape[-2]
    return "normal", fan_in ** -0.5


def layer_weights(params, l):
    g = params["groups"][0]["sub0"]
    a, f = g["attn"], g["ffn"]
    return {"n1": g["norm1"][l], "n2": g["norm2"][l], "wq": a["wq"][l],
            "wk": a["wk"][l], "wv": a["wv"][l], "wo": a["wo"][l],
            "wg": f["wi_gate"][l], "wu": f["wi_up"][l], "wd": f["wo"][l]}


@functools.partial(jax.jit, static_argnames=("eps", "theta", "control"))
def _layer(x, w, *, eps, theta, control):
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    S = x.shape[0]
    KV, Dh = w["wk"].shape[1], w["wk"].shape[2]
    G = w["wq"].shape[1] // KV
    pos = jnp.arange(S)
    h = rms(x, w["n1"], eps)
    q = rope(mm("sd,dhk->shk", h, w["wq"], control), pos, theta)
    k = rope(mm("sd,dhk->shk", h, w["wk"], control), pos, theta)
    v = mm("sd,dhk->shk", h, w["wv"], control)
    o = causal_gqa(q.reshape(S, KV, G, Dh), k, v, pos, control)
    x = x + mm("shk,hkd->sd", o, w["wo"], control)
    h = rms(x, w["n2"], eps)
    f = jax.nn.silu(mm("sd,df->sf", h, w["wg"], control)) * mm(
        "sd,df->sf", h, w["wu"], control)
    return x + mm("sf,fd->sd", f, w["wd"], control)


def layer(config, control):
    return functools.partial(_layer, eps=float(config["rms_norm_eps"]),
                             theta=float(config["rope_theta"]),
                             control=control)


class _Cost:
    def __init__(self, config):
        D, F = config["hidden_size"], config["intermediate_size"]
        self.per_token = 2.0 * config["num_hidden_layers"] * 3 * D * F

    def prefill_flops(self, prompt):
        return self.per_token * prompt

    def decode_flops(self, context):
        return self.per_token


def cost(config):
    return _Cost(config)
'''

RUN = """
import importlib.util, json, sys
sys.path[:0] = ["bench/tests"]
import tiny
spec = importlib.util.spec_from_file_location("bench_run", "bench/run.py")
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)
from harness import spec as S
c = tiny.cell()
c.config["model_type"] = "gqa_plain"
res, _ = run.execute(c, 2**33 + 3, 1.0, False, log=lambda *a, **k: None)
model = S.load_model(c.config)
print(json.dumps({"correct": res["correct"], "checks": res["checks"],
                  "metrics": sorted(res["metrics"]), "module": model.__file__,
                  "qk_norm": model.model_config(c.config).qk_norm}))
"""


def _digests(bench) -> dict:
    return {str(p.relative_to(bench)): hashlib.sha256(p.read_bytes()
                                                      ).hexdigest()
            for p in sorted(bench.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_new_model_type_is_one_new_file(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path / "bench")
    (tmp_path / "bench/models/gqa_plain.py").write_text(GQA_PLAIN)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run([sys.executable, "-c", RUN], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["module"] == str(tmp_path / "bench/models/gqa_plain.py")
    assert res["qk_norm"] is False
    assert res["metrics"] == ["itl_p99_s", "output_tokens_per_s", "setup_s"]
    after = _digests(tmp_path / "bench")
    assert set(after) - set(before) == {"models/gqa_plain.py"}
    assert all(after[k] == v for k, v in before.items())
