"""Chip smoke test: serve qwen3-4b at its published widths on a TPU.

    python chip_smoke.py               # one chip: phases (a)-(e)
    python chip_smoke.py --four-chips  # tensor-parallel mesh "4" vs one chip

Drives the normal serving path — ``launch.serve.build_runtime`` ->
``Runtime`` -> ``ServeEngine`` -> compiled Pallas kernels — with random
bf16 weights made from a seed, in this one process (a chip belongs to one
process at a time).  Phases on one chip:

  (a) refuse to run unless JAX's first device is a TPU (no CPU fallback);
  (b) build the Runtime and check every hot-path kernel resolves to
      compiled Pallas;
  (c) prefill 2 x 512 tokens + 8 decode steps through the Pallas kernels
      and through the jnp reference path (same params, same chip) and
      bound the max-abs logit difference relative to max |logit|;
  (d) serve 8 requests (prompts of 136-2048 tokens, 32 new tokens each)
      on the dense engine, then on the paged engine with the chunked-
      prefill scheduler: every request must finish with exactly 32 tokens
      and no retry, evacuation or fault event;
  (e) report peak device memory.

``--four-chips`` runs only the tensor-parallel check: the same model on
mesh "4" (heads, kv-heads and FFN columns over the 'model' axis, every
kernel inside ``shard_map``) against one device of the same process, with
the logit bound of (c) and a per-device memory balance check.

Timings printed here are information only, not a benchmark.  Any failure
exits non-zero; on success the last stdout line is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-4b"
CAPACITY = 4096          # dense KV: 36 L x 2 x 8 x 128 x 2 B x 4096 x 4 = 2.4 GB
SLOTS = 4
NEW_TOKENS = 32
PROMPT_LENS = (136, 160, 200, 256, 1100, 1400, 1800, 2048)
CHUNK = 256              # scheduler prefill chunk for the paged run
PARITY_BATCH, PARITY_LEN, PARITY_STEPS = 2, 512, 8
# Max |logit difference| / max |logit| between the Pallas kernels and the
# jnp reference.  Both run bf16 activations and weights; they round at
# different points (the kernels keep f32 accumulators and round once per
# kernel output, the reference rounds every einsum output to bf16, 2^-8
# relative), and those few-ulp differences compound through 36 residual
# layers.  A kernel bug — a wrong mask, head, block or scale — moves logits
# by O(1) of their range, far above this bound.
LOGIT_BOUND = 5e-2
SEED = 0


def log(msg: str) -> None:
    print(msg, flush=True)


def check_device(min_count: int) -> dict:
    """(a) The device JAX found: a TPU, or the run stops here."""
    import jax
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    log(f"(a) device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found {d.platform!r}")
    if len(devs) < min_count:
        raise SystemExit(f"need {min_count} chips, JAX found {len(devs)}")
    return info


def build(mesh: str = "", **kw):
    """(b) The qwen3-4b Runtime with bf16 params, Pallas on every hot path."""
    from repro.kernels import ops
    from repro.launch.serve import build_runtime
    rt = build_runtime(ARCH, mesh=mesh, capacity=CAPACITY, bf16_params=True,
                       seed=SEED, **kw)
    impls = {"train_attn": rt.train_attn_impl, "ffn": rt.fused_ffn_impl,
             "decode_attn": rt.decode_attn_impl}
    native = "paged" if rt.kv_layout == "paged" else "pallas"
    want = {"train_attn": "pallas", "ffn": "pallas", "decode_attn": native}
    if impls != want or ops._interpret():
        raise RuntimeError(f"kernels not compiled Pallas: {impls} "
                           f"interpret={ops._interpret()}")
    return rt


def describe_line(rt, key: str) -> str:
    return next(l for l in rt.describe().splitlines()
                if l.strip().startswith(key)).strip()


def parity_inputs(vocab: int):
    import numpy as np
    rng = np.random.default_rng(SEED)
    prompt = rng.integers(0, vocab, (PARITY_BATCH, PARITY_LEN), np.int32)
    steps = rng.integers(0, vocab, (PARITY_BATCH, PARITY_STEPS), np.int32)
    return prompt, steps


def logit_trace(rt, prompt, steps):
    """Last-position logits of a prefill plus each teacher-forced decode
    step: [1 + steps, B, vocab] float32 on the host."""
    import jax.numpy as jnp
    import numpy as np
    vocab = rt.cfg.vocab_size
    logits, caches = rt.prefill({"tokens": jnp.asarray(prompt)},
                                last_only=True)
    out = [logits[:, -1, :vocab]]
    pos = jnp.full((prompt.shape[0],), prompt.shape[1], jnp.int32)
    for i in range(steps.shape[1]):
        logits, caches = rt.decode_step(jnp.asarray(steps[:, i:i + 1]),
                                        caches, pos + i)
        out.append(logits[:, -1, :vocab])
    return np.stack([np.asarray(x.astype(jnp.float32)) for x in out])


def compare(label: str, got, want) -> float:
    import numpy as np
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise RuntimeError(f"{label}: non-finite logits")
    rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    log(f"    {label}: shape={got.shape} max|logit|={np.max(np.abs(want)):.4f}"
        f" max|diff|/max|logit|={rel:.3e} (bound {LOGIT_BOUND:.0e}) "
        f"argmax agreement={agree:.3f}")
    if rel > LOGIT_BOUND:
        raise RuntimeError(f"{label}: logit difference {rel:.3e} exceeds "
                           f"{LOGIT_BOUND:.0e}")
    return rel


def requests(vocab: int):
    import numpy as np
    from repro.serve.engine import Request
    rng = np.random.default_rng(SEED + 1)
    return [Request(rid=i, prompt=rng.integers(0, vocab, n, np.int32),
                    max_new_tokens=NEW_TOKENS)
            for i, n in enumerate(PROMPT_LENS)]


def serve_phase(label: str, rt, **engine_kw):
    """Serve the request set; checks every stream and the fault counters.
    Returns the engine (its caches stay live until the caller drops it)."""
    from repro.launch.serve import serve
    t0 = time.perf_counter()
    eng = serve(rt, requests(rt.cfg.vocab_size), slots=SLOTS, injector=None,
                **engine_kw)
    wall = time.perf_counter() - t0
    st = eng.stats
    lens = sorted(len(r.generated) for r in eng.finished)
    if lens != [NEW_TOKENS] * len(PROMPT_LENS):
        raise RuntimeError(f"{label}: tokens per request {lens}")
    if st.tick_retries or st.evacuations or eng.ft_events:
        raise RuntimeError(f"{label}: retries={st.tick_retries} "
                           f"evacuations={st.evacuations} "
                           f"ft_events={eng.ft_events[:3]}")
    lat = eng.latency_summary()
    log(f"    {label}: {st.summary}")
    log(f"    {label}: {sum(lens)} tokens in {wall:.2f} s (incl. "
        f"compilation) = {sum(lens) / wall:.1f} tok/s; ttft "
        f"p50={lat['ttft_p50']:.3f}s p95={lat['ttft_p95']:.3f}s; itl "
        f"p50={lat['itl_p50']:.4f}s p95={lat['itl_p95']:.4f}s")
    return eng


def gib(n: int) -> str:
    return f"{n / 2 ** 30:.2f} GiB"


def one_chip(dev) -> None:
    import gc
    rt = build()
    log("(b) " + describe_line(rt, "kernels"))

    prompt, steps = parity_inputs(rt.cfg.vocab_size)
    log(f"(c) parity: {PARITY_BATCH}x{PARITY_LEN} prefill + {PARITY_STEPS} "
        f"decode steps, Pallas vs jnp reference")
    fast = logit_trace(rt, prompt, steps)
    ref = logit_trace(rt.reshape(attn_impl="ref", ffn_impl="ref",
                                 capacity=CAPACITY), prompt, steps)
    compare("pallas vs ref", fast, ref)

    log(f"(d) serve {len(PROMPT_LENS)} requests, prompts "
        f"{PROMPT_LENS[0]}-{PROMPT_LENS[-1]}, {NEW_TOKENS} new tokens each")
    eng = serve_phase("dense", rt)
    dense = {r.rid: list(r.generated) for r in eng.finished}
    del eng
    gc.collect()
    paged_rt = build(kv_layout="paged", scheduler=True, chunk_size=CHUNK,
                     params=rt.params)
    eng = serve_phase("paged+scheduler", paged_rt)
    same = sum(dense[r.rid] == list(r.generated) for r in eng.finished)
    log(f"    dense vs paged+scheduler: {same}/{len(dense)} streams "
        f"token-identical (information: bf16 greedy ties)")
    del eng
    gc.collect()

    stats = dev.memory_stats() or {}
    log(f"(e) peak HBM {gib(stats.get('peak_bytes_in_use', 0))} of "
        f"{gib(stats.get('bytes_limit', 0))}")


def four_chips(devs) -> None:
    import gc

    import jax
    import jax.numpy as jnp
    from repro.kernels.partition import partition_report
    rt4 = build(mesh="4")
    log("    " + describe_line(rt4, "partition"))
    report = partition_report(rt4.cfg, rt4.plan, rt4.caps, rt4.partition)
    split = {k: report[k] for k in ("flash_train", "fused_ffn", "flash_decode")}
    if not all("/4@model" in v for v in split.values()):
        raise RuntimeError(f"a kernel is not split over the model axis: "
                           f"{split}")
    prompt, steps = parity_inputs(rt4.cfg.vocab_size)

    # the kernels must run inside shard_map on the TP mesh
    logits, caches = rt4.prefill({"tokens": jnp.asarray(prompt)},
                                 last_only=True)
    pos = jnp.full((PARITY_BATCH,), PARITY_LEN, jnp.int32)
    for name, fn, args in (
            ("prefill", lambda t: rt4.prefill({"tokens": t}, last_only=True),
             (jnp.asarray(prompt),)),
            ("decode", lambda t, c, p: rt4.decode_step(t, c, p),
             (jnp.asarray(steps[:, :1]), caches, pos))):
        text = str(jax.make_jaxpr(fn)(*args))
        if "shard_map" not in text or "pallas_call" not in text:
            raise RuntimeError(f"{name}: kernels did not dispatch through "
                               f"shard_map on mesh 4")
        log(f"    {name}: pallas_call inside shard_map "
            f"({text.count('pallas_call')} kernel call sites)")
    del logits, caches

    eng = serve_phase("tp4 dense", rt4)
    used = [(d.memory_stats() or {}).get("bytes_in_use", 0)
            for d in devs[:4]]
    mean = sum(used) / len(used)
    log("    bytes_in_use per device: "
        + ", ".join(gib(u) for u in used) + f" (mean {gib(mean)})")
    if max(used) > 1.5 * mean:
        raise RuntimeError("device memory unbalanced: the TP mesh did not "
                           "shard the model")
    del eng
    gc.collect()

    tp4 = logit_trace(rt4, prompt, steps)
    rt1 = build(params=jax.device_put(rt4.params, devs[0]))
    one = logit_trace(rt1, prompt, steps)
    compare("tp4 vs one device", tp4, one)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tensor-parallel check on 4 chips")
    args = ap.parse_args(argv)
    info = check_device(4 if args.four_chips else 1)

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    log(f"    compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(jax.devices())
    else:
        one_chip(jax.devices()[0])
    log(f"done in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
