"""The tracer's profiler sink, request spans and compile instants, and the
engine spans that split its waits from its host work.

The contracts under test:

- While enabled, every span enters a profiler annotation of its own name
  (a ``step`` span a step annotation); while disabled, nothing is
  entered and the shared no-op context comes back.
- ``record`` keeps a finished span at depth 0 under its id, and the
  Chrome export writes it as an async begin/end pair with that id.
- The engine opens ``collect:wait`` inside ``collect``, and
  ``admit:prefill`` / ``admit:wait`` inside ``admit``, which it opens
  only on ticks that find requests waiting; each admitted request gets
  one ``req:queued`` span from submission to admission.
- One process-wide compile listener marks a ``compile`` instant on every
  enabled tracer and counts ``jax_compiles_total`` in every live
  registry, however many ``Telemetry`` objects exist.
"""
import contextlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring

from repro import obs
from repro.configs import get_smoke_config
from repro.obs import COMPILES, Telemetry
from repro.obs.trace import DEFAULT_CAPACITY, Tracer
from repro.runtime import Runtime
from repro.serve.engine import Request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sink(calls):
    def annotate(name, **kw):
        calls.append((name, kw))
        return contextlib.nullcontext()
    return annotate


# ---------------------------------------------------------------------------
# tracer


def test_profiler_sink_follows_the_enabled_flag():
    calls = []
    tr = Tracer()
    tr.annotate = tr.annotate_step = _sink(calls)
    with tr.step("tick", 3, tick=3):
        with tr.span("collect"):
            pass
    assert calls == [] and not tr.events
    tr.enable()
    with tr.step("tick", 4, tick=4):
        with tr.span("collect"):
            pass
    assert calls == [("tick", {"step_num": 4}), ("collect", {})]
    assert [s.name for s in tr.events] == ["collect", "tick"]
    assert tr.spans("tick")[0].args == {"tick": 4}


def test_default_sinks_are_the_profilers_annotations():
    assert Tracer.annotate is jax.profiler.TraceAnnotation
    assert Tracer.annotate_step is jax.profiler.StepTraceAnnotation
    tr = Tracer(enabled=True)
    with tr.step("tick", 1):      # no capture running: annotations are free
        with tr.span("dispatch"):
            pass
    assert [s.depth for s in tr.events] == [1, 0]


def test_record_keeps_overlapping_spans_at_depth_zero():
    tr = Tracer(enabled=True)
    with tr.span("tick"):
        tr.record("req:queued", 1.0, 1.5, id=7, slot=0)
        tr.record("req:queued", 1.2, 1.6, id=8, slot=1)
    q = tr.spans("req:queued")
    assert [(s.id, s.depth) for s in q] == [(7, 0), (8, 0)]
    assert q[0].ts_us == pytest.approx(1.0e6)
    assert q[0].dur_us == pytest.approx(0.5e6)
    assert q[1].args == {"slot": 1}
    off = Tracer()
    off.record("req:queued", 1.0, 2.0, id=1)
    assert not off.events


def test_default_ring_holds_a_chat_window():
    """A 20 s window of a 16-slot chat engine records ~10k spans: the
    default ring holds several times that without dropping."""
    tr = Tracer(enabled=True)
    assert tr.capacity == DEFAULT_CAPACITY >= 50_000
    for i in range(DEFAULT_CAPACITY):
        tr.instant("x")
    assert tr.dropped == 0 and len(tr.events) == DEFAULT_CAPACITY
    tr.instant("x")
    assert tr.dropped == 1 and len(tr.events) == DEFAULT_CAPACITY


def test_chrome_export_writes_request_spans_as_async_pairs(tmp_path):
    tr = Tracer(enabled=True)
    with tr.step("tick", 1, tick=1):
        with tr.span("admit"):
            tr.record("req:queued", 2.0, 2.25, id=5, slot=1)
    tr.instant("compile", seconds=0.5)
    path = tr.export_chrome(str(tmp_path / "trace.json"))
    with open(path) as f:
        evs = json.load(f)["traceEvents"]
    pairs = [e for e in evs if e["name"] == "req:queued"]
    assert [e["ph"] for e in pairs] == ["b", "e"]
    assert all(e["id"] == 5 and e["cat"] == "request" for e in pairs)
    assert pairs[1]["ts"] - pairs[0]["ts"] == pytest.approx(0.25e6)
    assert pairs[0]["args"] == {"slot": 1}
    assert {e["name"]: e["ph"] for e in evs if e["name"] != "req:queued"} \
        == {"tick": "X", "admit": "X", "compile": "i"}


# ---------------------------------------------------------------------------
# engine spans


def _cfg():
    return get_smoke_config("llama3.2-3b").scaled(dtype=jnp.float32)


def _requests(cfg, n=5, seed=11):
    rng = np.random.default_rng(seed)
    return [Request(rid=100 + i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=int(rng.integers(3, 14)),
                                        dtype=np.int32),
                    max_new_tokens=int(rng.integers(3, 7)))
            for i in range(n)]


@pytest.fixture(scope="module")
def traced_run():
    """Five requests through two slots, ticked by hand so that the queue
    length each tick starts with is known."""
    cfg = _cfg()
    rt = Runtime.create(cfg, None, shape_kind="decode", capacity=32)
    eng = rt.engine(num_slots=2, trace=True)
    reqs = _requests(cfg)
    for r in reqs:
        eng.submit(r)
    waiting = {}
    for _ in range(200):
        waiting[eng._tick_no + 1] = len(eng.queue)
        if not eng.tick() and not eng.queue:
            break
    assert len(eng.finished) == len(reqs)
    return eng, reqs, waiting


def _inside(child, parent):
    return (parent.ts_us <= child.ts_us + 1 and child.ts_us + child.dur_us
            <= parent.ts_us + parent.dur_us + 1)


def _spans(tr, name):
    return [s for s in tr.events if s.name == name]


def test_collect_wait_nests_in_collect(traced_run):
    eng, _, _ = traced_run
    tr = eng.tracer
    collects, waits = _spans(tr, "collect"), _spans(tr, "collect:wait")
    assert waits and len(waits) == len(collects)
    for w in waits:
        owner = [c for c in collects if _inside(w, c)]
        assert len(owner) == 1 and w.depth == owner[0].depth + 1
        assert w.args["tick"] == owner[0].args["tick"]


def test_admit_children_nest_in_admit_on_admitting_ticks(traced_run):
    eng, _, _ = traced_run
    tr = eng.tracer
    admits = _spans(tr, "admit")
    admitting = {s.args["tick"] for s in admits}
    for name in ("admit:prefill", "admit:wait"):
        kids = _spans(tr, name)
        assert kids
        for k in kids:
            owner = [a for a in admits if _inside(k, a)]
            assert len(owner) == 1 and k.depth == owner[0].depth + 1
            assert k.args["tick"] in admitting
    # one prefill and one wait per admitted group, in that order
    assert len(_spans(tr, "admit:prefill")) == len(_spans(tr, "admit:wait")) \
        == eng.stats.prefill_calls


def test_admit_span_only_when_requests_wait(traced_run):
    eng, _, waiting = traced_run
    admit_ticks = {s.args["tick"] for s in _spans(eng.tracer, "admit")}
    ticks = {s.args["tick"] for s in _spans(eng.tracer, "tick")}
    assert admit_ticks == {t for t in ticks if waiting[t] > 0}
    assert admit_ticks != ticks        # some ticks found the queue empty


def test_queue_wait_is_one_span_per_admitted_request(traced_run):
    eng, reqs, _ = traced_run
    q = _spans(eng.tracer, "req:queued")
    assert sorted(s.id for s in q) == sorted(r.rid for r in reqs)
    by_rid = {r.rid: r for r in reqs}
    for s in q:
        r = by_rid[s.id]
        assert s.depth == 0
        assert s.ts_us == pytest.approx(r.submitted_at * 1e6)
        assert s.ts_us + s.dur_us == pytest.approx(r.admitted_at * 1e6)
    # the later requests waited for a slot: their spans cross ticks
    assert max(s.dur_us for s in q) > min(
        t.dur_us for t in _spans(eng.tracer, "tick"))


# ---------------------------------------------------------------------------
# compile listener


def test_compile_listener_marks_instant_and_counts_once():
    a = Telemetry()
    before = list(monitoring.get_event_duration_listeners())
    b = Telemetry()
    after = monitoring.get_event_duration_listeners()
    assert after == before and after.count(obs._on_jax_event) == 1
    assert obs.watching_compiles()
    a.tracer.enable()
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    f(np.ones(3, np.float32))
    n_a = a.registry.get(COMPILES).value
    n_b = b.registry.get(COMPILES).value
    marks = len(a.tracer.spans("compile"))
    f(np.ones(5, np.float32))           # a new shape: one recompile
    assert a.registry.get(COMPILES).value == n_a + 1
    assert b.registry.get(COMPILES).value == n_b + 1
    new = a.tracer.spans("compile")[marks:]
    assert len(new) == 1 and new[0].dur_us is None
    assert new[0].args["seconds"] > 0
    assert not b.tracer.events          # disabled tracer: counter only
    f(np.ones(5, np.float32))           # cached: nothing
    assert a.registry.get(COMPILES).value == n_a + 1


def test_launcher_writes_child_spans_and_compile_counter(tmp_path):
    trace, metrics = tmp_path / "trace.json", tmp_path / "metrics.json"
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "exanode-100m",
         "--smoke", "--requests", "4", "--max-new", "4", "--slots", "2",
         "--capacity", "32", "--no-preflight", "--trace-out", str(trace),
         "--metrics-out", str(metrics)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    names = {e["name"] for e in json.loads(trace.read_text())["traceEvents"]}
    assert {"tick", "collect", "collect:wait", "admit", "admit:prefill",
            "admit:wait", "req:queued", "compile"} <= names
    assert json.loads(metrics.read_text())[COMPILES] > 0


# ---------------------------------------------------------------------------
# named scopes


@pytest.mark.parametrize("path", ["forward", "decode"])
def test_decoder_layer_matmuls_sit_in_attn_or_ffn(path):
    """Every matmul of a cross-attention decoder layer carries the ``attn``
    or ``ffn`` scope on both paths (cross-attention under ``attn``), so a
    split by scope groups the same work alike; the decode cache write is
    ``attn/kv_update``."""
    import re

    from repro.models.blocks import block_decode, block_forward, block_specs
    from repro.models.common import LayerGroup, ModelConfig, abstract_params
    from repro.serve.kvcache import _kind_cache
    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=256,
                      head_dim=16, groups=(LayerGroup(("attn_cross",), 1),))
    p = abstract_params(block_specs("attn_cross", cfg))
    B, S, T = 2, 8, 6
    if path == "forward":
        def f(x, p, mem):
            return block_forward("attn_cross", x, p, cfg,
                                 positions=jnp.tile(jnp.arange(S), (B, 1)),
                                 attn_mode="heads", memory=mem)
        args = (jax.ShapeDtypeStruct((B, S, 64), cfg.dtype), p,
                jax.ShapeDtypeStruct((B, T, 64), cfg.dtype))
    else:
        def f(x, p, cache):
            pos = jnp.zeros((B,), jnp.int32)
            return block_decode("attn_cross", x, p, cfg, cache, pos=pos,
                                write_idx=pos, layer=0)

        def one_layer_stack():      # the self-attention leaves as carried
            c = _kind_cache("attn_cross", cfg, B, 16, T)
            return {k: a[None] if k in ("k", "v", "pos") else a
                    for k, a in c.items()}
        args = (jax.ShapeDtypeStruct((B, 1, 64), cfg.dtype), p,
                jax.eval_shape(one_layer_stack))
    txt = jax.jit(f).lower(*args).as_text(debug_info=True)
    dots = re.findall(r'loc\("jit\(f\)/([^"]*)/dot_general"', txt)
    scopes = {d.split("/")[0] for d in dots}
    assert scopes == {"attn", "ffn"}, dots
    # self q/k/v/o and cross q/k/v/o projections, the two attends of
    # each, and the two FFN matmuls
    assert sum(d.startswith("attn/") for d in dots) >= 8
    if path == "decode":
        assert re.search(r'loc\("jit\(f\)/attn/kv_update/scatter"', txt)
