"""Unified observability layer: metrics registry + structured tracer.

The paper's MCM is validated by *continuous measurement* — IBERT
bit-error-ratio monitors on every inter-FPGA link, DDR memory tests on
every bank — and the serving stack follows the same discipline: every
subsystem (engine, scheduler, blockpool, fault tolerance, link layer)
reports into one :class:`~repro.obs.metrics.MetricsRegistry` and one
:class:`~repro.obs.trace.Tracer` so a single snapshot shows the whole
machine.

``Telemetry`` is the small container the :class:`repro.runtime.Runtime`
hands out (``rt.telemetry()``): a registry, a tracer, and helpers to
export both.  Modules that can run stand-alone (blockpool, scheduler,
straggler monitor) accept ``registry=None`` and fall back to
``NULL_REGISTRY`` so instrumentation is free when nobody is looking.

Every ``Telemetry`` also watches JAX's compiles: one process-wide
``jax.monitoring`` listener on the backend-compile event increments
``jax_compiles_total`` in each live Telemetry's registry and marks a
``compile`` instant (with the compile's seconds) on each enabled tracer.
An instant, not a span: compiles also happen outside ticks.
"""
from __future__ import annotations

import threading
import weakref
from dataclasses import dataclass, field

import jax

from repro.obs.metrics import (
    NULL_REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    latency_fields,
    summarize,
)
from repro.obs.trace import NULL_TRACER, Span, Tracer

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NULL_REGISTRY",
    "NULL_TRACER",
    "Span",
    "Telemetry",
    "Tracer",
    "latency_fields",
    "summarize",
    "watching_compiles",
]


COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILES = "jax_compiles_total"
_watched: list = []         # weak references to live Telemetry objects
_listening = False
_watch_lock = threading.Lock()   # compiles may finish on several threads


def _on_jax_event(event: str, secs: float, **kw) -> None:
    if event != COMPILE_EVENT:
        return
    with _watch_lock:
        live = [t for t in (ref() for ref in _watched) if t is not None]
        _watched[:] = [weakref.ref(t) for t in live]
    for t in live:
        t.registry.counter(COMPILES).inc()
        t.tracer.instant("compile", seconds=secs, **kw)


def _watch_compiles(telemetry: "Telemetry") -> None:
    global _listening
    with _watch_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_event)
            _listening = True
        _watched.append(weakref.ref(telemetry))


def watching_compiles() -> bool:
    """Whether the compile listener is installed (some ``Telemetry`` has
    been built): compiles are then marked and counted."""
    return _listening


@dataclass
class Telemetry:
    """Registry + tracer pair owned by a Runtime and shared by its engine.

    Survives ``Runtime.reshape`` (live evacuation builds a new Runtime but
    carries the same Telemetry across), so counters stay monotonic over a
    mesh change and the tick timeline is continuous.
    """

    registry: MetricsRegistry = field(default_factory=MetricsRegistry)
    tracer: Tracer = field(default_factory=Tracer)

    def __post_init__(self):
        self.registry.counter(COMPILES, "XLA programs compiled (or loaded "
                              "from the compilation cache) since start")
        _watch_compiles(self)

    def snapshot(self) -> dict:
        return self.registry.snapshot()

    def exposition(self) -> str:
        return self.registry.exposition()

    def describe(self) -> str:
        n = self.registry.describe()
        t = self.tracer
        state = "on" if t.enabled else "off"
        return (f"{n} | tracer {state} "
                f"({len(t.events)}/{t.capacity} spans buffered)")
