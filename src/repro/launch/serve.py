"""Serving launcher: preflight -> Runtime -> engine -> batched requests.

    PYTHONPATH=src python -m repro.launch.serve --arch exanode-100m \
        --smoke --requests 8 --max-new 16 [--mesh 2x4]

Builds a decode-shaped ``repro.runtime.Runtime``, runs the
continuous-batching engine (serve/engine.py) over synthetic prompts and
reports throughput/latency percentiles — the serving-side end-to-end
driver.

Fault-tolerance knobs: ``--health-every N`` gates every Nth tick on
device health checks, ``--tick-retries`` bounds the transient-failure
retry loop, and ``--fault-plan`` (or the ``REPRO_FAULT_PLAN`` env var)
arms a scripted fault plan — e.g. ``tick=6,kind=raise,times=3`` forces a
live evacuation mid-run; the engine's ft event log is streamed as JSONL
(one JSON object per line) to ``--events-out`` (default stdout).

Observability: ``--metrics-out FILE`` dumps the telemetry registry at
exit (``.json`` -> snapshot, else Prometheus text exposition),
``--trace-out FILE`` enables the tracer and writes a Chrome
``trace_event`` file viewable in chrome://tracing or Perfetto.

Data-integrity knobs: ``--burn-in`` runs the full qualification gate
(DDR-style memory test per device + PRBS link sweep with BER bounds)
before serving, and ``--scrub-every N`` arms the engine's corruption
scrub — with ``--fault-plan 'tick=6,kind=corrupt,target=kv,seed=7'`` the
whole detect -> quarantine -> replay path runs live.

``--bf16-params`` materializes the weights in bf16 (a 4 B-parameter model
is 16 GB in f32, 8 GB in bf16).  ``build_runtime`` and ``serve`` are the
launcher's two steps as functions — ``chip_smoke.py`` drives them.
"""
from __future__ import annotations

import argparse

import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.ft.inject import FaultInjector
from repro.launch import preflight as pf
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import mesh_from_spec
from repro.obs.export import dump_metrics, write_events_jsonl
from repro.obs.metrics import percentile
from repro.runtime import Runtime
from repro.serve.engine import Request, ServeEngine


def build_runtime(arch: str, *, smoke: bool = False, mesh: str = "",
                  capacity: int = 128, kv_layout: str = "dense",
                  kv_dtype: str = "f32", scheduler: bool = False,
                  token_budget: int = 0, chunk_size: int = 0,
                  bf16_params: bool = False, params=None,
                  seed: int = 0) -> Runtime:
    """The decode-shaped Runtime the launcher serves from (``mesh`` is a
    spec string, "" = single device; ``params`` reuses weights)."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    sched_kw = {}
    if token_budget:
        sched_kw["token_budget"] = token_budget
    if chunk_size:
        sched_kw["chunk_size"] = chunk_size
    return Runtime.create(cfg, mesh_from_spec(mesh) if mesh else None,
                          shape_kind="decode", capacity=capacity,
                          kv_layout=kv_layout, kv_dtype=kv_dtype,
                          scheduler=scheduler, sched_kw=sched_kw or None,
                          param_dtype=(jnp.bfloat16 if bf16_params
                                       else jnp.float32),
                          seed=seed, params=params)


def serve(rt: Runtime, requests, *, slots: int = 4,
          **engine_kw) -> ServeEngine:
    """Build ``rt``'s engine, submit ``requests`` and run them to
    completion; returns the engine (``finished``, ``stats``,
    ``ft_events``, ``latency_summary()``)."""
    eng = rt.engine(num_slots=slots, **engine_kw)
    for r in requests:
        eng.submit(r)
    eng.run_to_completion()
    return eng


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="exanode-100m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--capacity", type=int, default=128)
    ap.add_argument("--mesh", default="")
    ap.add_argument("--bf16-params", action="store_true",
                    help="materialize the weights in bf16 instead of f32")
    ap.add_argument("--kv-layout", default="dense",
                    choices=("dense", "paged"),
                    help="serve KV layout: dense per-slot slabs or the "
                         "pooled paged block caches (serve/blockpool.py; "
                         "arch-gated by caps.supports_paged_decode)")
    ap.add_argument("--kv-dtype", default="f32", choices=("f32", "int8"),
                    help="paged pool storage: f32, or int8 blocks with "
                         "per-(entry, kv-head) scales dequantized inside "
                         "the decode kernel (requires --kv-layout paged; "
                         "arch-gated by caps.supports_quantized_kv)")
    ap.add_argument("--no-preflight", action="store_true")
    ap.add_argument("--burn-in", action="store_true",
                    help="full qualification gate before serving: DDR-style "
                         "memory test on every device + PRBS link sweep "
                         "with BER bounds (launch/preflight.run_burn_in); "
                         "refuses to serve on any failure")
    ap.add_argument("--health-every", type=int, default=0,
                    help="run device health checks every N ticks (0 = off)")
    ap.add_argument("--scrub-every", type=int, default=0,
                    help="integrity scrub cadence in ticks (0 = off): seal "
                         "KV fingerprints, re-verify them + the params "
                         "checksum, quarantine + replay on corruption")
    ap.add_argument("--tick-retries", type=int, default=2,
                    help="transient tick failures retried before evacuating")
    ap.add_argument("--fault-plan", default="",
                    help="scripted fault plan (ft/inject.py grammar, e.g. "
                         "'tick=6,kind=raise,times=3'); defaults to "
                         "$REPRO_FAULT_PLAN")
    ap.add_argument("--scheduler", action="store_true",
                    help="token-budget continuous batching: chunked prefill "
                         "interleaved with decode (serve/scheduler.py)")
    ap.add_argument("--token-budget", type=int, default=0,
                    help="scheduler per-tick token budget (0 = default)")
    ap.add_argument("--chunk-size", type=int, default=0,
                    help="scheduler prefill chunk length (0 = default)")
    ap.add_argument("--events-out", default="-",
                    help="JSONL sink for engine ft events (one JSON object "
                         "per line; '-' = stdout)")
    ap.add_argument("--metrics-out", default="",
                    help="write the telemetry registry at exit: .json -> "
                         "snapshot, anything else -> Prometheus text "
                         "exposition ('-' = stdout)")
    ap.add_argument("--trace-out", default="",
                    help="enable the tracer and write a Chrome trace_event "
                         "file at exit (chrome://tracing / Perfetto)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    rt = build_runtime(args.arch, smoke=args.smoke, mesh=args.mesh,
                       capacity=args.capacity, kv_layout=args.kv_layout,
                       kv_dtype=args.kv_dtype, scheduler=args.scheduler,
                       token_budget=args.token_budget,
                       chunk_size=args.chunk_size,
                       bf16_params=args.bf16_params)
    cfg, mesh = rt.cfg, rt.mesh
    if args.trace_out:
        rt.telemetry().tracer.enable()

    if args.burn_in:
        rep = rt.burn_in()
        print(rep.summary(), flush=True)
        if not rep.ok:
            raise SystemExit("burn-in failed: this machine does not "
                             "qualify (see tables above)")

    print(rt.describe(), flush=True)

    if mesh and not args.no_preflight:
        with mesh:
            rep = pf.run_preflight(mesh)
            print(rep.summary(), flush=True)
            if not rep.ok:
                raise SystemExit("preflight failed")

    ft_kw = dict(health_every=args.health_every,
                 tick_retries=args.tick_retries,
                 scrub_every=args.scrub_every)
    if args.fault_plan:
        ft_kw["injector"] = FaultInjector.parse(args.fault_plan)
    rng = np.random.default_rng(0)
    requests = [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            size=args.prompt_len,
                                            dtype=np.int32),
                        max_new_tokens=args.max_new)
                for i in range(args.requests)]
    eng = serve(rt, requests, slots=args.slots, **ft_kw)
    print("engine:", eng.stats.summary)
    if eng.ft_events:
        n = write_events_jsonl(eng.ft_events, args.events_out)
        if args.events_out not in ("", "-"):
            print(f"ft events: {n} -> {args.events_out}")

    # latency percentiles over finished requests (shared obs helpers —
    # same math as engine.latency_summary / bench_serve)
    lat = [r.finished_at - r.submitted_at for r in eng.finished]
    ttft = [r.first_token_at - r.submitted_at for r in eng.finished]
    if lat:
        print(f"latency  p50={percentile(lat, 50):.3f}s "
              f"p95={percentile(lat, 95):.3f}s")
        print(f"ttft     p50={percentile(ttft, 50):.3f}s "
              f"p95={percentile(ttft, 95):.3f}s")
        ls = eng.latency_summary()
        print(f"itl      p50={ls['itl_p50']:.4f}s p95={ls['itl_p95']:.4f}s "
              f"p99={ls['itl_p99']:.4f}s  "
              f"queue_wait p95={ls['queue_wait_p95']:.4f}s")
    if args.metrics_out:
        dump_metrics(rt.telemetry().registry, args.metrics_out)
        if args.metrics_out != "-":
            print(f"metrics -> {args.metrics_out}")
    if args.trace_out:
        rt.telemetry().tracer.export_chrome(args.trace_out)
        print(f"trace -> {args.trace_out}")
    print("done")


if __name__ == "__main__":
    main()
