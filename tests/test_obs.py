"""Observability suite: metrics registry, tracer, exporters, engine wiring.

The contracts under test:

- Instruments are typed: counters are monotonic (``inc`` rejects negative
  deltas, ``set`` rejects regressions), histograms keep bucket counts +
  a bounded reservoir, labelled families key children correctly.
- The shared percentile helpers match ``numpy.percentile`` (linear
  method), and ``engine.latency_summary()`` / the bench ``_lat_fields``
  key shapes are pinned to them.
- Spans nest and never cross tick boundaries; the Chrome export
  round-trips through ``json.loads`` with valid ``ph``/``ts``/``dur``.
- One registry snapshot surfaces engine + scheduler + blockpool + ft +
  link instruments together.
- Exactly-once counting: a run that retries ticks and evacuates ends
  with registry counters equal to the engine's own stats (the counter's
  monotonic ``set`` would raise on any double-count regression), and
  token streams are bitwise-identical with tracing on vs off.

The 8-device variants (mesh-shrink evacuation with telemetry carried
across ``Runtime.reshape``) need the forced CPU topology
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``; scripts/ci.sh
runs this file under both topologies) and skip elsewhere.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.linktest import LinkMonitor, LinkReport
from repro.ft.inject import FaultInjector
from repro.ft.straggler import StragglerMonitor
from repro.obs import Telemetry
from repro.obs.export import JsonlExporter, dump_metrics, write_events_jsonl
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NULL_REGISTRY,
    latency_fields,
    percentile,
    summarize,
)
from repro.obs.trace import Tracer
from repro.runtime import Runtime
from repro.serve.engine import EngineStats, Request
from repro.serve.scheduler import Scheduler

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(scripts/ci.sh runs this gate)")

ARCH = "llama3.2-3b"


def _cfg():
    return get_smoke_config(ARCH).scaled(dtype=jnp.float32)


def _stream(cfg, n=4, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=int(rng.integers(3, 14)),
                                        dtype=np.int32),
                    max_new_tokens=int(rng.integers(4, 9)))
            for i in range(n)]


# ---------------------------------------------------------------------------
# metrics registry


def test_counter_monotonic():
    c = Counter("x_total")
    c.inc()
    c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)
    c.set(5)
    with pytest.raises(ValueError):
        c.set(4)
    assert c.value == 5


def test_gauge_moves_both_ways():
    g = Gauge("depth")
    g.set(4)
    g.dec()
    g.inc(0.5)
    assert g.value == 3.5


def test_histogram_buckets_and_reservoir():
    h = Histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(55.55)
    assert h._counts == [1, 1, 1, 1]       # one per bucket + inf tail
    assert h.percentile(50) == pytest.approx(
        float(np.percentile([0.05, 0.5, 5.0, 50.0], 50)))
    s = h.summary()
    assert s["count"] == 4 and s["max"] == 50.0


def test_labelled_families():
    reg = MetricsRegistry()
    c = reg.counter("events_total", "help", labels=("event",))
    c.labels(event="a").inc()
    c.labels(event="a").inc()
    c.labels(event="b").inc(3)
    snap = reg.snapshot()["events_total"]
    by = {s["labels"]["event"]: s["value"] for s in snap}
    assert by == {"a": 2, "b": 3}
    h = reg.histogram("hl", labels=("axis",), buckets=(1.0, 2.0))
    h.labels(axis="data").observe(1.5)
    assert h.labels(axis="data").buckets == (1.0, 2.0)
    assert h.labels(axis="data").count == 1


def test_registry_kind_mismatch_and_identity():
    reg = MetricsRegistry()
    c1 = reg.counter("n_total")
    assert reg.counter("n_total") is c1
    with pytest.raises(TypeError):
        reg.gauge("n_total")
    assert "n_total" in reg and reg.names() == ["n_total"]


def test_exposition_format():
    reg = MetricsRegistry()
    reg.counter("a_total", "things").inc(2)
    reg.histogram("h", "lat", buckets=(1.0,)).observe(0.5)
    reg.gauge("g", labels=("axis",)).labels(axis="data").set(1.5)
    text = reg.exposition()
    assert "# HELP a_total things" in text
    assert "# TYPE a_total counter" in text
    assert "a_total 2" in text
    assert 'h_bucket{le="1"} 1' in text
    assert 'h_bucket{le="+Inf"} 1' in text
    assert "h_count 1" in text
    assert 'g{axis="data"} 1.5' in text


def test_null_registry_is_inert():
    c = NULL_REGISTRY.counter("whatever")
    c.inc()
    c.labels(x=1).observe(3)
    assert NULL_REGISTRY.snapshot() == {}
    assert "whatever" not in NULL_REGISTRY


# ---------------------------------------------------------------------------
# shared percentile math (the dedup contract)


def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    xs = rng.exponential(size=101).tolist()
    for q in (0, 25, 50, 95, 99, 100):
        assert percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)), rel=1e-12)
    assert percentile([], 50) == 0.0
    assert percentile([7.0], 95) == 7.0


def test_summarize_and_latency_fields_shapes():
    s = summarize([1.0, 2.0, 3.0])
    assert set(s) == {"count", "min", "max", "mean", "p50", "p95", "p99"}
    f = latency_fields("ttft", [1.0, 2.0])
    assert set(f) == {"ttft_p50", "ttft_p95", "ttft_p99"}


def test_latency_summary_shape_pinned():
    """engine.latency_summary() keys and values must match the legacy
    np.percentile implementation exactly — the dedup must not change
    BENCH_serve.json's shape."""
    cfg = _cfg()
    rt = Runtime.create(cfg, None, shape_kind="decode", capacity=32)
    eng = rt.engine(num_slots=2)
    for r in _stream(cfg):
        eng.submit(r)
    eng.run_to_completion()
    ls = eng.latency_summary()
    assert set(ls) == {"requests",
                       "ttft_p50", "ttft_p95", "ttft_p99",
                       "itl_p50", "itl_p95", "itl_p99",
                       "queue_wait_p50", "queue_wait_p95", "queue_wait_p99"}
    ttfts = [r.first_token_at - r.submitted_at for r in eng.finished]
    assert ls["ttft_p95"] == pytest.approx(
        float(np.percentile(ttfts, 95)), rel=1e-12)


# ---------------------------------------------------------------------------
# tracer


def test_disabled_tracer_is_noop():
    tr = Tracer()
    ctx = tr.span("tick")
    assert tr.span("other") is ctx          # shared null context
    with ctx:
        pass
    tr.instant("ev")
    assert not tr.events


def test_spans_nest_and_record_depth():
    tr = Tracer(enabled=True)
    with tr.span("tick", tick=1):
        with tr.span("dispatch"):
            pass
        with tr.span("collect"):
            pass
    names = [s.name for s in tr.events]
    assert names == ["dispatch", "collect", "tick"]  # children exit first
    depths = {s.name: s.depth for s in tr.events}
    assert depths == {"tick": 0, "dispatch": 1, "collect": 1}
    tick = tr.spans("tick")[0]
    for child in tr.spans("dispatch") + tr.spans("collect"):
        assert tick.ts_us <= child.ts_us
        assert child.ts_us + child.dur_us <= tick.ts_us + tick.dur_us + 1


def test_ring_buffer_bounds_memory():
    tr = Tracer(capacity=4, enabled=True)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.events) == 4
    assert tr.dropped == 6
    assert [s.name for s in tr.events] == ["s6", "s7", "s8", "s9"]


def test_span_records_error():
    tr = Tracer(enabled=True)
    with pytest.raises(RuntimeError):
        with tr.span("bad"):
            raise RuntimeError("boom")
    assert tr.events[-1].args["error"] == "RuntimeError"


def test_chrome_trace_round_trips(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("tick", tick=1):
        pass
    tr.instant("ft:evacuate", tick=1)
    path = tr.export_chrome(str(tmp_path / "trace.json"))
    with open(path) as f:
        ct = json.load(f)
    evs = ct["traceEvents"]
    assert len(evs) == 2
    for e in evs:
        assert e["ph"] in ("X", "i")
        assert isinstance(e["ts"], (int, float))
        assert "pid" in e and "tid" in e
    complete = [e for e in evs if e["ph"] == "X"]
    assert complete and all(e["dur"] >= 0 for e in complete)
    instants = [e for e in evs if e["ph"] == "i"]
    assert instants and all(e["s"] == "t" for e in instants)


# ---------------------------------------------------------------------------
# exporters


def test_jsonl_exporter(tmp_path):
    path = str(tmp_path / "events.jsonl")
    events = [{"event": "evacuate", "tick": 3},
              {"event": "corruption", "regions": [4, 5]}]
    assert write_events_jsonl(events, path) == 2
    lines = open(path).read().splitlines()
    assert [json.loads(ln) for ln in lines] == events


def test_jsonl_exporter_handles_numpy(tmp_path):
    path = str(tmp_path / "np.jsonl")
    with JsonlExporter(path) as ex:
        ex.emit({"v": np.int32(7), "f": np.float64(0.5)})
    assert json.loads(open(path).read()) == {"v": 7, "f": 0.5}


def test_dump_metrics_formats(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a_total").inc(2)
    jpath = str(tmp_path / "m.json")
    dump_metrics(reg, jpath)
    assert json.load(open(jpath)) == {"a_total": 2}
    tpath = str(tmp_path / "m.prom")
    dump_metrics(reg, tpath)
    assert "# TYPE a_total counter" in open(tpath).read()


# ---------------------------------------------------------------------------
# subsystem wiring (host-only)


def test_scheduler_instruments():
    reg = MetricsRegistry()
    sched = Scheduler(token_budget=8, chunk_size=4, registry=reg)

    class R:
        def __init__(self, rid, priority=0):
            self.rid, self.priority = rid, priority

    sched.enqueue(R(1))
    sched.enqueue(R(2, priority=1))
    snap = reg.snapshot()
    depths = {s["labels"]["cls"]: s["value"]
              for s in snap["sched_queue_depth"]}
    assert depths == {0: 1, 1: 1}
    assert sched.select() is not None
    assert reg.get("sched_selected_total").value == 1
    assert sched.chunk_tokens(active_decodes=6, remaining=4) == 2
    assert reg.get("sched_shrunk_chunks_total").value == 1
    assert reg.get("sched_budget_utilization").value == pytest.approx(1.0)
    assert sched.chunk_tokens(active_decodes=8, remaining=4) == 0
    assert reg.get("sched_deferred_chunks_total").value == 1


def test_straggler_histogram_visible_before_escalation():
    reg = MetricsRegistry()
    mon = StragglerMonitor(window=8, sustained=3, registry=reg)
    for i in range(5):
        mon.observe(i, 0.01)
    h = reg.get("straggler_step_seconds")
    assert h.count == 5                     # every observation recorded
    assert reg.get("straggler_median_seconds").value == pytest.approx(0.01)
    # no warn/remesh fired, yet the rolling window is already exported
    assert all(r.action == "ok" for r in mon.history)


def test_link_monitor_rolling_ber_and_derate():
    reg = MetricsRegistry()
    mon = LinkMonitor(window=2, registry=reg)

    def rep(errors):
        return LinkReport(axis="data", size=2, payload_bytes=1024,
                          bit_errors=errors, checks={}, elapsed_s=0.01,
                          eff_bandwidth=1e6)

    mon.record([rep(0)])
    assert mon.current_ber()["data"] == 0.0
    mon.record([rep(49152)])               # bits_moved = 1024*3*2*8 = 49152
    # window of 2: (0 + 49152) / (2 * 49152) = 0.5
    assert mon.current_ber()["data"] == pytest.approx(0.5)
    mon.record([rep(49152)])               # oldest (clean) sweep rolls off
    assert mon.current_ber()["data"] == pytest.approx(1.0)
    assert reg.get("link_sweeps_total").value == 3
    assert reg.get("link_bit_errors_total").value == 2 * 49152
    ber = {s["labels"]["axis"]: s["value"] for s in reg.snapshot()["link_ber"]}
    assert ber["data"] == pytest.approx(1.0)

    class FakeFabric:
        def with_link_ber(self, axis_ber):
            return ("derated", dict(axis_ber))

    assert mon.derate(FakeFabric()) == ("derated", {"data": 1.0})


def test_engine_stats_bind_rejects_regression():
    reg = MetricsRegistry()
    st = EngineStats()
    st.bind(reg)
    st.tokens_out += 3
    assert reg.get("serve_engine_tokens_out_total").value == 3
    with pytest.raises(ValueError):
        st.tokens_out = 1                  # a double-count rollback raises
    # the dataclass view never saw the regression either
    assert st.tokens_out == 3


def test_engine_stats_rebind_offsets():
    """A fresh EngineStats binding to a registry that already accumulated
    (two engines on one Runtime, or post-evacuation) must not reset or
    trip the counters."""
    reg = MetricsRegistry()
    a = EngineStats()
    a.bind(reg)
    a.ticks += 5
    b = EngineStats()
    b.bind(reg)                            # counter sits at 5, stats at 0
    b.ticks += 2
    assert b.ticks == 2
    assert reg.get("serve_engine_ticks_total").value == 7


# ---------------------------------------------------------------------------
# engine integration (single device)


def test_one_snapshot_surfaces_every_subsystem():
    cfg = _cfg()
    rt = Runtime.create(cfg, None, shape_kind="decode", capacity=32,
                        kv_layout="paged", scheduler=True)
    eng = rt.engine(num_slots=2)
    for r in _stream(cfg):
        eng.submit(r)
    eng.run_to_completion()
    eng.apply_link_reports([LinkReport(
        axis="data", size=2, payload_bytes=1024, bit_errors=0, checks={},
        elapsed_s=0.01, eff_bandwidth=1e6)])
    snap = rt.telemetry().snapshot()
    for name in ("serve_engine_tokens_out_total",   # engine
                 "serve_queue_depth",
                 "sched_selected_total",            # scheduler
                 "sched_budget_utilization",
                 "blockpool_used_blocks",           # blockpool
                 "blockpool_prefix_misses_total",
                 "straggler_step_seconds",          # ft
                 "serve_ft_events_total",
                 "link_ber",                        # link layer
                 "link_sweeps_total"):
        assert name in snap, f"snapshot missing {name}"
    assert snap["serve_engine_tokens_out_total"] == eng.stats.tokens_out
    assert snap["blockpool_used_blocks"] == 0.0     # all released
    # and the text exposition renders the same registry
    assert "serve_engine_tokens_out_total" in rt.telemetry().exposition()


def test_spans_nest_within_ticks_and_streams_match():
    cfg = _cfg()

    def run(trace):
        rt = Runtime.create(cfg, None, shape_kind="decode", capacity=32)
        eng = rt.engine(num_slots=2, trace=trace)
        for r in _stream(cfg):
            eng.submit(r)
        eng.run_to_completion()
        return rt, {r.rid: list(r.generated) for r in eng.finished}

    rt_off, toks_off = run(False)
    rt_on, toks_on = run(True)
    # tracing must not perturb the computation
    assert toks_off == toks_on
    assert not rt_off.telemetry().tracer.events

    tr = rt_on.telemetry().tracer
    ticks = tr.spans("tick")
    assert ticks, "no tick spans recorded"
    # tick spans never overlap each other (no span crosses a tick boundary)
    ordered = sorted(ticks, key=lambda s: s.ts_us)
    for a, b in zip(ordered, ordered[1:]):
        assert a.ts_us + a.dur_us <= b.ts_us + 1
    # every phase span is contained in exactly one tick interval; a
    # request's span (it carries the request id) is not a phase: its
    # queue wait starts before the tick that admits it
    for child in tr.events:
        if child.name == "tick" or child.dur_us is None \
                or child.id is not None:
            continue
        owners = [t for t in ticks
                  if t.ts_us <= child.ts_us + 1
                  and child.ts_us + child.dur_us <= t.ts_us + t.dur_us + 1]
        assert len(owners) == 1, (child.name, len(owners))
        assert child.depth >= 1
    # the chrome export of the real engine run round-trips
    ct = tr.chrome_trace()
    json.loads(json.dumps(ct))
    assert any(e["name"] == "tick" and e["ph"] == "X" and e["dur"] > 0
               for e in ct["traceEvents"])


def test_counters_exact_under_retry_and_evacuation():
    """The exactly-once contract: a run that retries a tick three times
    and live-evacuates must end with registry counters equal to the
    engine's own stats and the same total tokens as a fault-free run —
    the monotonic Counter.set would have raised on any double-count."""
    cfg = _cfg()

    def run(injector=None):
        rt = Runtime.create(cfg, None, shape_kind="decode", capacity=32)
        eng = rt.engine(num_slots=2, injector=injector,
                        tick_retries=2, retry_backoff_s=0.001)
        for r in _stream(cfg):
            eng.submit(r)
        eng.run_to_completion()
        return rt, eng

    _, clean = run()
    rt, eng = run(FaultInjector.parse("tick=6,kind=raise,times=3"))
    assert eng.stats.evacuations == 1
    assert eng.stats.tick_retries >= 1
    reg = rt.telemetry().registry
    for k in ("ticks", "tokens_out", "admitted", "finished",
              "tick_retries", "evacuations", "streams_replayed"):
        assert reg.get(f"serve_engine_{k}_total").value == \
            getattr(eng.stats, k), k
    # zero tokens lost or double-counted vs the fault-free run
    assert {r.rid: list(r.generated) for r in eng.finished} == \
        {r.rid: list(r.generated) for r in clean.finished}
    evs = {s["labels"]["event"]: s["value"]
           for s in reg.snapshot()["serve_ft_events_total"]}
    assert evs.get("evacuate") == 1
    assert reg.get("ft_evacuation_seconds").count == 1


def test_ft_events_jsonl_round_trip(tmp_path):
    cfg = _cfg()
    rt = Runtime.create(cfg, None, shape_kind="decode", capacity=32)
    eng = rt.engine(num_slots=2, tick_retries=2, retry_backoff_s=0.001,
                    injector=FaultInjector.parse("tick=6,kind=raise,times=3"))
    for r in _stream(cfg):
        eng.submit(r)
    eng.run_to_completion()
    path = str(tmp_path / "events.jsonl")
    n = write_events_jsonl(eng.ft_events, path)
    lines = open(path).read().splitlines()
    assert n == len(lines) == len(eng.ft_events) > 0
    kinds = [json.loads(ln)["event"] for ln in lines]
    assert "evacuate" in kinds


def test_telemetry_describe_in_runtime():
    cfg = _cfg()
    rt = Runtime.create(cfg, None, shape_kind="decode", capacity=32)
    assert "not wired" in rt.describe()
    rt.engine(num_slots=2)
    desc = rt.describe()
    assert "obs" in desc and "instruments" in desc and "tracer off" in desc


# ---------------------------------------------------------------------------
# 8-device variants


@needs8
def test_telemetry_survives_mesh_shrink_evacuation():
    """Counters must stay monotonic across a real mesh-shrink evacuation:
    the engine rebuilds its Runtime via reshape, but the Telemetry (and
    its registry) is carried over, so one timeline covers both meshes."""
    from repro.launch.mesh import mesh_from_spec
    cfg = _cfg()
    rt = Runtime.create(cfg, mesh_from_spec("2x4"), shape_kind="decode",
                        capacity=32)
    reg = rt.telemetry().registry
    victim = jax.devices()[7].id
    eng = rt.engine(num_slots=2, health_every=2, retry_backoff_s=0.001,
                    injector=FaultInjector.parse(
                        f"tick=2,kind=fail,device={victim}"))
    for r in _stream(cfg):
        eng.submit(r)
    eng.run_to_completion()
    assert eng.stats.evacuations == 1
    # the rebuilt Runtime hands out the same Telemetry object
    assert eng.rt is not rt
    assert eng.rt.telemetry() is rt.telemetry()
    assert eng.obs.registry is reg
    for k in ("ticks", "tokens_out", "evacuations", "health_checks"):
        assert reg.get(f"serve_engine_{k}_total").value == \
            getattr(eng.stats, k), k
    assert reg.get("ft_health_check_seconds").count == \
        eng.stats.health_checks


@needs8
def test_link_monitor_feeds_burn_in_and_gate():
    from repro.launch.mesh import mesh_from_spec
    cfg = _cfg()
    rt = Runtime.create(cfg, mesh_from_spec("2x4"), shape_kind="decode",
                        capacity=32)
    rep = rt.burn_in(mem_bytes=1 << 12, link_payload=1 << 10)
    assert rep.ok
    ber = rt.link_monitor().current_ber()
    assert set(ber) == set(rt.mesh.axis_names)
    assert all(v == 0.0 for v in ber.values())
    snap = rt.telemetry().snapshot()
    axes = {s["labels"]["axis"] for s in snap["link_ber"]}
    assert axes == set(rt.mesh.axis_names)
    assert snap["link_sweeps_total"] == len(rt.mesh.axis_names)
