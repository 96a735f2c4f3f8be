#!/usr/bin/env bash
# CI gate: bytecode-compile + tier-1 test suite + registry and serve smokes.
#
#     bash scripts/ci.sh
#
# Mirrors ROADMAP.md's tier-1 verify command and adds (a) a compileall pass
# so syntax errors anywhere in src/ fail fast, (b) the all-arch registry
# smoke (every configs.ARCHS entry builds a Runtime whose prefill/decode
# match the raw model-family surface bit-for-bit), and (c) the serve
# fast-path smoke benchmark so data-path regressions (admission batching,
# donation, kernel fallback) are caught even when no unit test covers the
# exact shape.  The serve smoke also refreshes BENCH_serve.json (tokens/s,
# admissions/s) at the repo root for the perf trajectory, and (d) the
# train-step smoke benchmark, which exercises the Pallas flash-attention +
# fused-FFN custom-VJP train path end to end and refreshes BENCH_step.json
# (fast-vs-ref step time per arch) beside it, and (e) the 8-device sharded
# kernel-dispatch gate: tests/test_partition.py (sharded-vs-replicated
# parity for every arch) plus the --mesh variants of both benchmarks,
# which merge sharded-vs-replicated numbers into the BENCH jsons, and
# (f) the 8-device fault-injection gate: tests/test_ft_serve.py drives
# scripted faults through health-gated evacuation onto a surviving mesh
# (2x4 -> 1x4) with token-identical streams and zero drops, and (g) the
# continuous-batching scheduler gate: tests/test_scheduler.py (chunked
# prefill == monolithic token parity, WRR/aging policy, mid-prefill
# evacuation replay; re-run under the 8-device mesh) plus the bench
# --scheduler SLO smoke, which asserts the scheduler's ITL p95 is >= 3x
# better than monolithic admission under a mixed long-prompt/decode load
# and merges the 'slo' section into BENCH_serve.json, and (h) the
# 8-device data-integrity gate: tests/test_integrity.py drives scripted
# bit flips (kind=corrupt) through the seal/scrub/quarantine/replay
# path — 100% detection, zero corrupted tokens, only affected streams
# replayed — plus burn-in, BER derating, and checkpoint CRC coverage,
# and (i) the observability gate: tests/test_obs.py (metrics registry /
# tracer / exporter contracts, span-vs-tick nesting, exactly-once
# counters across retry + evacuation; re-run under the 8-device mesh)
# plus a trace-artifact check: the Chrome trace_event file the serve
# smoke emits (BENCH_serve_trace.json) must parse with valid ph/ts/dur,
# and (j) the quantized-KV gate: tests/test_quant_kv.py (block-quant
# properties, q8 kernel vs oracle, f32-vs-int8 paged greedy parity with
# bounded logit drift, int8-pool integrity recovery) plus the bench
# --kv-dtype int8 quantized section (KV footprint <= 15% of dense).
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== compileall =="
python -m compileall -q src

echo "== all-arch registry smoke =="
python -m pytest -q tests/test_registry.py

echo "== paged==dense token-parity subset =="
# the paged KV subsystem's acceptance gate: every paged-capable arch must
# produce token-identical streams under both layouts, and the allocator /
# kernel invariants must hold
python -m pytest -q tests/test_paged.py

echo "== tier-1 pytest =="
# registry + paged suites already ran above; the partition and ft-serve
# suites run in their own 8-device gates below — skip the re-runs
# (ROADMAP's tier-1 command without --ignore covers them when run
# standalone)
python -m pytest -x -q --ignore=tests/test_registry.py \
    --ignore=tests/test_paged.py --ignore=tests/test_partition.py \
    --ignore=tests/test_ft_serve.py --ignore=tests/test_scheduler.py \
    --ignore=tests/test_integrity.py --ignore=tests/test_obs.py \
    --ignore=tests/test_quant_kv.py

echo "== quantized-KV gate =="
# int8 paged-pool acceptance: block-quant math properties, q8 kernel ==
# dequant oracle, per-arch f32-paged vs int8-paged greedy token parity
# with bounded logit drift, integrity corrupt/quarantine/replay on the
# int8 pool, and the dequant-counter / footprint-gauge obs wiring
python -m pytest -q tests/test_quant_kv.py

echo "== serve fast-path smoke benchmark (dense + paged + int8 engines) =="
# --kv-layout paged adds the dense-vs-paged section and asserts the paged
# KV footprint stays <= 50% of the dense slabs for the smoke workload;
# --kv-dtype int8 adds the quantized section (footprint <= 15% of dense,
# >= 95% greedy-token match vs the f32 paged run)
python -m benchmarks.bench_serve --smoke --kv-layout paged --kv-dtype int8

echo "== train-step fast-path smoke benchmark =="
python -m benchmarks.bench_step --smoke

echo "== 8-device sharded kernel-dispatch gate =="
# the shard_map partition layer's acceptance gate: every arch's
# sharded-vs-replicated parity (loss/grads 1e-4, logits 1e-3, identical
# decode streams) on a forced 8-device CPU mesh, then the bench --mesh
# variants, which merge sharded-vs-replicated numbers into the BENCH jsons
# written by the plain smokes above.  The XLA_FLAGS override is scoped to
# these commands only: everything above must keep seeing the real single
# CPU device (tests/conftest.py documents the same rule for the suite).
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -q tests/test_partition.py
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m benchmarks.bench_step --smoke --mesh 2x4
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m benchmarks.bench_serve --smoke --mesh 2x2

echo "== 8-device fault-injection gate =="
# fault-tolerant serving acceptance: scripted faults (ft/inject.py) force
# health-gated / straggler / retry-exhaustion evacuations, including the
# real mesh shrink (2x4 -> 1x4 after losing a device) with token-identical
# streams and zero drops; single-device variants of these tests also run
# under plain tier-1
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -q tests/test_ft_serve.py

echo "== continuous-batching scheduler gate =="
# chunked-prefill-interleaved-with-decode acceptance: token streams must
# be bitwise-identical to the monolithic engine (dense + paged), the
# WRR/aging policy invariants must hold, and a mid-prefill evacuation
# must replay the partially-prefilled prompt exactly once.  Runs on the
# real single device first, then again under the forced 8-device mesh
# so the chunked mixed step is exercised through the partition layer.
python -m pytest -q tests/test_scheduler.py
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -q tests/test_scheduler.py
# SLO smoke: monolithic vs scheduler on a mixed long-prompt/decode load;
# asserts ITL p95 >= 3x better with identical streams and merges the
# 'slo' section into BENCH_serve.json
python -m benchmarks.bench_serve --smoke --scheduler

echo "== 8-device data-integrity gate =="
# silent-data-corruption acceptance: scripted bit flips (kind=corrupt,
# target=kv|params|collective) must be detected 100% of the time with
# zero corrupted tokens emitted; corrupted blocks quarantine and only
# the affected streams replay (token-identical, streams_dropped == 0).
# Also covers fingerprint/flip property coverage, burn-in (memtest +
# PRBS links with BER bounds), link-BER fabric derating + mesh demotion
# (2x4 data-axis link loss), and checkpoint/snapshot CRC32.
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -q tests/test_integrity.py

echo "== observability gate =="
# unified telemetry acceptance: one registry snapshot must surface
# engine + scheduler + blockpool + ft + link instruments together,
# counters must stay exactly-once across tick retry / evacuation /
# replay (the monotonic Counter raises on any double-count), spans must
# nest inside tick boundaries, and token streams must be bitwise
# identical with tracing on vs off.  Single device first, then the
# 8-device variants (telemetry carried across a real mesh-shrink
# evacuation; burn-in feeding the link monitor).
python -m pytest -q tests/test_obs.py
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -q tests/test_obs.py
# trace-artifact check: the serve smoke above ran with tracing enabled
# for its overhead section and exported BENCH_serve_trace.json; it must
# be a valid Chrome trace_event file with tick spans
python - <<'EOF'
import json
ct = json.load(open("BENCH_serve_trace.json"))
evs = ct["traceEvents"]
assert evs, "trace has no events"
assert all(e["ph"] in ("X", "i", "b", "e") and "ts" in e for e in evs)
ticks = [e for e in evs if e["name"] == "tick" and e["ph"] == "X"]
assert ticks and all(e["dur"] > 0 for e in ticks)
print(f"trace artifact OK: {len(evs)} events, {len(ticks)} tick spans")
EOF

echo "CI OK"
