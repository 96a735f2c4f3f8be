"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's model through the program's normal serving path with
weights made from the seed, warms every shape the cell's traffic can cause
(set-up), serves the traffic for ``--seconds`` (the window), checks what the
window served against the plain reference, and prints one JSON object as
the last line of standard output.  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` records a profiler trace of the window
and reports the per-layer metrics instead.

Exits non-zero, printing no result, unless JAX's devices are TPUs and
there are as many as the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

from harness import check, stats  # noqa: E402
from harness import trace as tr  # noqa: E402
from harness.peaks import peaks  # noqa: E402
from harness.spec import load_cell, load_model, load_reader  # noqa: E402

TRACE_DIR = ROOT / ".bench_out" / "trace"


def process_age() -> float:
    """Seconds since this process started (from /proc, else since this
    module was first executed)."""
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        start = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1]
                    .split()[19]) / ticks
        up = float(Path("/proc/uptime").read_text().split()[0])
        return up - start
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_START


def enable_compile_cache() -> None:
    """The program's persistent compilation cache (``.jax_cache`` in the
    checkout unless ``JAX_COMPILATION_CACHE_DIR`` names another), keeping
    every program, however quick to compile, so that no run after the
    first compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _host_spans(tracer) -> list:
    """The program's spans: (name, start, end, depth), perf_counter s; an
    instant event has end None."""
    return [(s.name, s.ts_us / 1e6,
             None if s.dur_us is None else (s.ts_us + s.dur_us) / 1e6,
             s.depth) for s in tracer.spans()]


def judge(numbers: dict, limits: dict) -> tuple[list, bool]:
    """(name, value, limit) for every number the cell's limits name, and
    whether each lies within its limit."""
    checks = [(k, numbers[k], limit) for k, limit in
              dict(limits, requests_without_first_token=0).items()
              if k in numbers]
    return checks, all(math.isfinite(v) and v <= limit
                       for _, v, limit in checks)


def compare(gaps: list, failed: int) -> dict:
    """The numbers compared, from the gaps of one side (the program, or
    the control in its place) and the requests that had no first token."""
    return {"max_logit_gap": check.widest(gaps),
            "mean_logit_gap": check.mean(gaps),
            "flip_share": check.flip_share(gaps),
            "requests_without_first_token": failed}


def execute(cell, seed: int, seconds: float, trace: bool, *,
            wrap_runtime=None, log=print, control: bool = False
            ) -> tuple[dict, list]:
    """One run of ``cell``; returns (result line, checks).  With
    ``control``, the control is also read on the same sample and judged
    at the same limits, under the result's key ``control``."""
    import jax
    from harness import serve
    from harness.traffic import Traffic

    counter = serve.CompileCounter()
    rt, eng = serve.build(cell, seed, wrap_runtime=wrap_runtime)
    vocab = int(cell.config["vocab_size"])
    serve.warm(eng, cell.traffic, vocab)
    traffic = Traffic(cell.traffic, vocab, seed)
    devices = (list(rt.mesh.devices.flatten()) if rt.mesh is not None
               else jax.devices()[:1])
    marks: dict = {}

    def on_start():
        marks["setup_s"] = process_age()
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            with jax.profiler.TraceAnnotation(tr.SYNC):
                marks["t_sync"] = time.perf_counter()
            eng.tracer.clear()
            eng.tracer.enable()

    def on_stop():
        if trace:
            jax.block_until_ready(eng.caches)
            jax.profiler.stop_trace()
            eng.tracer.disable()

    try:
        w = serve.run_window(eng, traffic, seconds, counter, on_start, on_stop)
    finally:
        counter.close()
    info = device_info()
    info["memory_peak_bytes"] = serve.memory_peak(devices)
    late = w.lateness or [0.0]
    log(f"window: {w.ticks} ticks, {len(w.recs)} requests due, "
        f"{w.compiles} compilations inside the window; generator late "
        f"p50 {stats.percentile(late, 50):.6f} s max {max(late):.6f} s",
        file=sys.stderr)

    result = {"correct": False, "attempted": len(w.recs),
              "failed": sum(1 for rc in w.recs if not rc.req.first_token_at),
              "metrics": {}, "device": info}
    if not trace:
        e2e = serve.end_to_end(w, seconds)
        e2e["setup_s"] = marks["setup_s"]
        log(f"served {e2e['_tokens']} tokens and {e2e['_gaps']} gaps "
            f"inside the window", file=sys.stderr)
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    else:
        t = tr.load(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        lo, hi = tr.window_ns(t, marks["t_sync"], w.t0, w.t_end)
        chips = [t["chips"][k] for k in sorted(t["chips"])]
        peak = peaks(info["kind"]) if info["platform"] == "tpu" else None
        ctx = {"trace": t, "lo": lo, "hi": hi, "window_s": seconds,
               "window": w, "chips": len(chips), "peak": peak,
               "config": cell.config,
               "cost": load_model(cell.config).cost(cell.config),
               "host_spans": _host_spans(eng.tracer)}
        for m in cell.per_layer:
            v = load_reader(m["name"])(ctx) if chips else None
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        if chips:
            busy = [tr.busy_ns(c, lo, hi) / 1e9 for c in chips]
            info["busy_s"] = sum(busy) / len(busy)
            info["window_s"] = (hi - lo) / 1e9
            result["breakdown"] = {
                "device_ops": tr.top_ops(t, lo, hi),
                "idle_gaps": tr.idle_gaps(
                    t, lo, hi, [h[:3] for h in ctx["host_spans"]
                                if h[3] and h[2] is not None],
                    marks["t_sync"])}

    # the comparison, once the window has closed and the engine is freed
    params = rt.params
    del eng
    gc.collect()
    picked = check.sample([rc.req for rc in w.recs], seed,
                          int(cell.traffic["check"]["min_tokens"]), w.held)
    t_ref = time.perf_counter()
    gaps = check.gaps(params, cell.config, picked, control=control)
    numbers = compare(gaps["program"], result["failed"])
    log(f"reference: {len(picked)} requests, "
        f"{sum(len(r.generated) for r in picked)} served tokens compared in "
        f"{time.perf_counter() - t_ref:.1f} s; " + ", ".join(
            f"{k} {v}" for k, v in numbers.items()), file=sys.stderr)
    checks, result["correct"] = judge(numbers, cell.limits)
    if control:
        ctl_numbers = compare(gaps["control"], result["failed"])
        ctl, ok = judge(ctl_numbers, cell.limits)
        result["control"] = {"correct": ok, "checks": {
            n: {"value": v, "limit": limit} for n, v, limit in ctl},
            "numbers": ctl_numbers, "program_numbers": numbers}
    result["checks"] = {n: {"value": v, "limit": limit}
                        for n, v, limit in checks}
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"{args.workload} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 3
    enable_compile_cache()
    result, checks = execute(cell, args.seed, args.seconds, bool(args.trace))
    for name, v, limit in checks:
        print(f"check {name}: {v} (limit {limit})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
