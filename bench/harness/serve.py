"""Drive the serving engine under a traffic mix for a fixed window.

The system under test is ``Runtime.create(...)`` -> ``rt.engine(...)``,
driven through its public ``submit`` and ``tick`` from this loop, so that
arrivals follow the mix's schedule.  Every request is timed from when it
was due: in a closed loop, the moment its client's previous reply
completed (plus the think time); in an open loop, its arrival time.

A closed loop is primed before its window opens: each client's first
request is submitted and served until it has its first decoded token, so
that the window sees every slot busy, as a loop that has run for a while
does, and not the opening admissions of an empty engine, nor the gaps
that span them (the first requests' prompts are admitted in groups, one
prefill after another, and a request's first gap waits for the groups
after its own).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from harness import stats
from harness.spec import Cell, load_model
from harness.traffic import Traffic, warm_shapes
from harness.weights import make_params

WARM_SEED = 12345
DRAIN_S = 60.0


@dataclass
class Rec:
    """One request of the window: the engine's request and its due time."""
    req: object
    due: float


@dataclass
class Window:
    t0: float
    t_end: float
    recs: list = field(default_factory=list)
    tick_contexts: list = field(default_factory=list)   # per decode tick
    lateness: list = field(default_factory=list)
    compiles: int = 0
    ticks: int = 0
    drained_at: float = 0.0
    held: list = field(default_factory=list)   # in a slot at the close


class CompileCounter:
    """Counts the programs compiled (or loaded from the persistent cache)
    while armed, through JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax._src import monitoring
        self.n, self.armed = 0, False
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if self.armed and name == self.EVENT:
            self.n += 1

    def close(self):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._on)


def build(cell: Cell, seed: int, *, wrap_runtime=None):
    """The Runtime with the benchmark's weights, and its engine."""
    from repro.launch.mesh import mesh_from_spec
    from repro.runtime import Runtime
    model = load_model(cell.config)
    cfg = model.model_config(cell.config)
    tp = int(cell.config["deployment"]["tensor_parallel"])
    eng_s = cell.traffic["engine"]
    mesh = mesh_from_spec(str(tp)) if tp > 1 else None
    rt = Runtime.create(cfg, mesh, shape_kind="decode",
                        capacity=eng_s["capacity"],
                        kv_layout=eng_s.get("kv_layout", "dense"),
                        param_dtype=jnp.bfloat16)
    rt.params = make_params(rt.specs, cfg.d_model, seed, model,
                            shardings=rt.param_shardings)
    jax.block_until_ready(rt.params)
    if wrap_runtime is not None:
        wrap_runtime(rt)
    eng = rt.engine(num_slots=eng_s["slots"],
                    max_admit=eng_s.get("max_admit"), injector=None)
    return rt, eng


def warm(eng, mix: dict, vocab: int) -> None:
    """Run every prefill shape the mix can cause, with the decode step and
    the admission splice behind each; then the first shape once more,
    since the first admission saw the caches as built and the later ones
    see them as the decode step leaves them."""
    from repro.serve.engine import Request
    rng = np.random.default_rng(WARM_SEED)
    shapes = warm_shapes(mix)
    rid = -1
    for _, count, length in shapes + shapes[:1]:
        for _ in range(count):
            eng.submit(Request(rid=rid, prompt=rng.integers(
                0, vocab, length, dtype=np.int32), max_new_tokens=2))
            rid -= 1
        eng.run_to_completion()
    jax.block_until_ready(eng.caches)
    eng.finished.clear()


def _contexts(live: dict) -> list:
    return [len(r.prompt) + len(r.generated) for r in live.values()
            if r.first_token_at and not r.done]


def run_window(eng, traffic: Traffic, seconds: float, counter: CompileCounter,
               on_start=None, on_stop=None) -> Window:
    """Serve the mix for ``seconds``; then keep ticking, with nothing new
    due, until every request due in the window has its first token.  A
    closed loop is primed first (module docstring); its first requests
    are in the window's records, due before it opened."""
    from repro.serve.engine import Request
    closed = traffic.closed
    clients = int(traffic.mix.get("clients", 0))
    think = float(traffic.mix.get("think_s", 0.0))
    w = Window(t0=0.0, t_end=0.0)
    live, owner = {}, {}
    i = 0

    def submit_due(due_free, now):
        nonlocal i
        for c in range(clients):
            if due_free[c] is not None and due_free[c] <= now:
                d = traffic.request(i)
                r = Request(rid=i, prompt=d.prompt, max_new_tokens=d.max_new)
                eng.submit(r)
                w.recs.append(Rec(r, due_free[c]))
                w.lateness.append(r.submitted_at - due_free[c])
                live[i], owner[i] = r, c
                due_free[c] = None
                i += 1

    if closed:
        submit_due([time.perf_counter()] * clients, time.perf_counter())
        limit = time.perf_counter() + DRAIN_S
        while (any(not (r.token_times or r.done) for r in live.values())
               and time.perf_counter() < limit):
            eng.tick()
        jax.block_until_ready(eng.caches)
    if on_start is not None:
        on_start()
    t0 = time.perf_counter()
    w.t0, w.t_end = t0, t0 + seconds
    due_free = [None] * clients
    arrivals = [] if closed else [t0 + a for a in traffic.arrivals(seconds)]
    nxt, nfin = 0, 0
    counter.armed = True
    start_compiles = counter.n
    while True:
        now = time.perf_counter()
        if now >= w.t_end:
            break
        if closed:
            submit_due(due_free, now)
        else:
            while nxt < len(arrivals) and arrivals[nxt] <= now:
                d = traffic.request(nxt)
                r = Request(rid=nxt, prompt=d.prompt, max_new_tokens=d.max_new)
                eng.submit(r)
                w.recs.append(Rec(r, arrivals[nxt]))
                w.lateness.append(r.submitted_at - arrivals[nxt])
                live[nxt] = r
                nxt += 1
        ctx = _contexts(live)
        if ctx:
            w.tick_contexts.append(ctx)
        busy = eng.tick()
        w.ticks += 1
        for r in eng.finished[nfin:]:
            live.pop(r.rid, None)
            if closed and r.rid in owner:
                due_free[owner.pop(r.rid)] = r.finished_at + think
        nfin = len(eng.finished)
        if not busy and not closed and nxt < len(arrivals):
            time.sleep(max(0.0, min(arrivals[nxt] - time.perf_counter(),
                                    0.001)))
    w.compiles = counter.n - start_compiles
    w.held = [r for r in live.values() if r.first_token_at and not r.done]
    if on_stop is not None:
        on_stop()
    limit = time.perf_counter() + DRAIN_S
    while (any(not rc.req.first_token_at for rc in w.recs)
           and time.perf_counter() < limit):
        eng.tick()
    w.drained_at = time.perf_counter()
    counter.armed = False
    return w


def end_to_end(w: Window, seconds: float) -> dict:
    """The host-clock metrics of a window (seconds): every token delivered
    inside it, and every gap between two tokens of a request that ends
    inside it."""
    tokens, gaps = 0, []
    for rc in w.recs:
        r = rc.req
        times = ([r.first_token_at] if r.first_token_at else []) + list(
            r.token_times)
        tokens += sum(1 for t in times if w.t0 <= t <= w.t_end)
        gaps.extend(b - a for a, b in zip(times, times[1:])
                    if w.t0 <= b <= w.t_end)
    return {"output_tokens_per_s": tokens / seconds,
            "itl_p99_s": stats.percentile(gaps, 99),
            "_tokens": tokens, "_gaps": len(gaps)}


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
