"""Training-step timing: Pallas fast path vs the jnp reference forward.

    PYTHONPATH=src python -m benchmarks.bench_step [--smoke]

For each representative arch the same smoke-scale train step (loss + grads
+ AdamW update through the ``repro.runtime`` surface) is timed twice — once
with ``attn_impl/ffn_impl="ref"`` (pure-jnp attention + SwiGLU) and once
with ``"pallas"`` (flash-attention + fused-FFN custom-VJP kernels) — and
the per-arch speedup lands in ``BENCH_step.json`` at the repo root, the
training-side sibling of ``BENCH_serve.json``, so the step-time trajectory
is machine-readable across PRs.

On CPU the Pallas kernels run in *interpret mode*: that validates the
numerics and the wiring (what CI needs) but is slower than XLA's fused jnp
path, so the recorded CPU "speedup" is < 1 by design.  The JSON records the
backend so downstream tooling can tell validation runs from real TPU
timings.  ``--smoke`` shrinks shapes/iters for CI; the decode-step timing
of the old bench lives on in ``bench_serve``.

``--mesh SPEC`` (e.g. ``2x4``; needs enough devices — CI forces 8 CPU
devices via XLA_FLAGS) times the same Pallas train step with the shard_map
kernel dispatch on vs off (``partition="auto"`` vs ``"off"``) and *merges*
a ``mesh`` section into the existing BENCH_step.json, so the plain-run
numbers survive.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from benchmarks.common import emit, merge_bench_json, time_fn
from repro.data.pipeline import DataConfig, synthetic_batch
from repro.runtime import Runtime

BENCH_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                          "BENCH_step.json")

ARCHS = ("exanode-100m", "llama3.2-3b", "mixtral-8x7b")
MESH_ARCHS = ("qwen3-4b", "mixtral-8x7b")   # heads-mode: kernels partition


def _time_train_step(arch: str, impl: str, B: int, S: int, iters: int,
                     mesh=None, partition: str = "auto") -> float:
    rt = Runtime.create(arch, mesh, smoke=True, shape_kind="train",
                        seq_len=S, attn_impl=impl, ffn_impl=impl,
                        partition=partition)
    step = rt.compile_train_step(donate=False)
    state = rt.init_train_state()
    dcfg = DataConfig(vocab_size=rt.cfg.vocab_size, seq_len=S, global_batch=B)
    batch = {k: jnp.asarray(v) for k, v in synthetic_batch(dcfg, 0).items()}
    return time_fn(lambda s, b: step(s, b)[1]["loss"], state, batch,
                   warmup=1, iters=iters)



def main_mesh(mesh_spec: str, smoke: bool = False):
    """Sharded-vs-replicated kernel dispatch on ``mesh_spec``."""
    from repro.launch.mesh import mesh_from_spec
    mesh = mesh_from_spec(mesh_spec)
    B, S = (2, 32) if smoke else (4, 64)
    iters = 3 if smoke else 5

    archs_record = {}
    for arch in MESH_ARCHS:
        t_rep = _time_train_step(arch, "pallas", B, S, iters, mesh=mesh,
                                 partition="off")
        t_shard = _time_train_step(arch, "pallas", B, S, iters, mesh=mesh,
                                   partition="auto")
        ratio = t_rep / t_shard
        emit(f"train_step_sharded_{arch}_{mesh_spec}", t_shard * 1e6,
             f"replicated_us={t_rep * 1e6:.0f} speedup={ratio:.2f}x")
        archs_record[arch] = {
            "replicated_us": round(t_rep * 1e6, 1),
            "sharded_us": round(t_shard * 1e6, 1),
            "speedup": round(ratio, 3),
        }
    backend = jax.default_backend()
    print(f"# sharded kernel dispatch ({backend}, mesh {mesh_spec}): "
          + "  ".join(f"{a}={r['speedup']:.2f}x"
                      for a, r in archs_record.items()), flush=True)
    if backend != "tpu":
        print("# note: non-TPU backend runs Pallas in interpret mode — "
              "numerics/wiring validation, not a speed measurement",
              flush=True)
    merge_bench_json(BENCH_JSON, {"mesh": {
        "spec": mesh_spec, "smoke": smoke, "backend": backend,
        "batch": B, "seq_len": S, "impl": "pallas",
        "pallas_interpret": backend != "tpu",
        "archs": archs_record,
    }})


def main(smoke: bool = False):
    B, S = (2, 32) if smoke else (4, 64)
    iters = 3 if smoke else 5
    backend = jax.default_backend()

    archs_record = {}
    for arch in ARCHS:
        t_ref = _time_train_step(arch, "ref", B, S, iters)
        t_fast = _time_train_step(arch, "pallas", B, S, iters)
        toks = B * S
        speedup = t_ref / t_fast
        emit(f"train_step_ref_{arch}_b{B}_s{S}", t_ref * 1e6,
             f"tok_per_s={toks / t_ref:.0f}")
        emit(f"train_step_pallas_{arch}_b{B}_s{S}", t_fast * 1e6,
             f"tok_per_s={toks / t_fast:.0f} speedup={speedup:.2f}x")
        archs_record[arch] = {
            "ref_us": round(t_ref * 1e6, 1),
            "pallas_us": round(t_fast * 1e6, 1),
            "speedup": round(speedup, 3),
            "tokens_per_s_pallas": round(toks / t_fast, 1),
        }

    print(f"# train fast path ({backend}): " + "  ".join(
        f"{a}={r['speedup']:.2f}x" for a, r in archs_record.items()),
        flush=True)
    if backend != "tpu":
        print("# note: non-TPU backend runs Pallas in interpret mode — "
              "numerics validation, not a speed measurement", flush=True)

    merge_bench_json(BENCH_JSON, {
        "smoke": smoke, "backend": backend, "batch": B, "seq_len": S,
        "pallas_interpret": backend != "tpu",
        "archs": archs_record,
    })


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default="",
                    help="mesh spec (e.g. 2x4): time sharded-vs-replicated "
                         "kernel dispatch and merge a 'mesh' section into "
                         "BENCH_step.json (skips the plain sections)")
    ns = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if ns.mesh:
        main_mesh(ns.mesh, smoke=ns.smoke)
    else:
        main(smoke=ns.smoke)
