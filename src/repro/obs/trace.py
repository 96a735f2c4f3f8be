"""Structured tracer: nested spans, ring buffer, Chrome trace export.

Spans are recorded with the same clock the engine stamps ``Request``
timestamps with (``time.perf_counter``), so per-request events line up
with tick-phase spans on one timeline.  The API is a context manager:

    with tracer.step("tick", 7, tick=7):
        with tracer.span("dispatch"):
            ...

Recording is a ring buffer (``collections.deque(maxlen=capacity)``):
old spans fall off, memory stays bounded, and the hot path is an
append + two clock reads.  A disabled tracer (the default, and the
shared ``NULL_TRACER``) short-circuits to a reusable no-op context
manager, so instrumented code pays one attribute check when tracing is
off — that is the overhead contract the serve bench asserts.

While enabled, every span also enters a ``jax.profiler.TraceAnnotation``
of the same name (``step`` spans a ``StepTraceAnnotation``), so a
profiler capture shows the program's spans, and one step per tick, on
the same clock as the device's ops.

``record`` adds a span whose start is known only after the fact (a
request's queue wait).  Such spans overlap one another, so they carry an
``id``, sit at depth 0, and are exported as async begin/end pairs.

Export is Chrome/Perfetto ``trace_event`` JSON: complete events
(``ph="X"`` with ``ts``/``dur`` in microseconds) for nested spans, async
``ph="b"``/``"e"`` pairs keyed by ``id`` for recorded spans, instant
events (``ph="i"``) for point occurrences like ft events and compiles.
Load the file in ``chrome://tracing`` or https://ui.perfetto.dev.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any

import jax

__all__ = ["Span", "Tracer", "NULL_TRACER"]

# a traced 20 s window of a 16-slot engine at ~50 ticks/s records ~10k
# spans (tick phases plus per-request spans): room for several such
DEFAULT_CAPACITY = 1 << 16


def _now_us() -> float:
    return time.perf_counter() * 1e6


@dataclass
class Span:
    """One completed span (or instant, when ``dur_us`` is None)."""

    name: str
    ts_us: float                    # start, perf_counter microseconds
    dur_us: float | None = None     # None => instant event
    depth: int = 0                  # nesting depth at record time
    args: dict = field(default_factory=dict)
    id: int | None = None           # set on ``record``ed (async) spans

    def to_events(self, pid: int, tid: int) -> list[dict]:
        ev: dict[str, Any] = {"name": self.name, "ts": self.ts_us,
                              "pid": pid, "tid": tid}
        if self.args:
            ev["args"] = self.args
        if self.id is not None:
            ev.update(ph="b", cat="request", id=self.id)
            end = {"name": self.name, "ph": "e", "cat": "request",
                   "id": self.id, "ts": self.ts_us + self.dur_us,
                   "pid": pid, "tid": tid}
            return [ev, end]
        if self.dur_us is not None:
            ev.update(ph="X", dur=self.dur_us)
        else:
            ev.update(ph="i", s="t")  # instant scope: thread
        return [ev]


class _NullSpanCtx:
    """Reusable no-op context manager — the disabled-tracer fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


_NULL_SPAN_CTX = _NullSpanCtx()


class _SpanCtx:
    """Live span: records on ``__exit__`` so nesting depth is exact."""

    __slots__ = ("tracer", "name", "args", "ts_us", "depth", "annotation")

    def __init__(self, tracer: "Tracer", name: str, args: dict, annotation):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.annotation = annotation

    def __enter__(self):
        self.depth = len(self.tracer._stack)
        self.tracer._stack.append(self.name)
        self.annotation.__enter__()
        self.ts_us = _now_us()
        return self

    def __exit__(self, exc_type, exc, tb):
        end = _now_us()
        self.annotation.__exit__(exc_type, exc, tb)
        stack = self.tracer._stack
        if stack and stack[-1] == self.name:
            stack.pop()
        if exc_type is not None:
            self.args = dict(self.args, error=exc_type.__name__)
        self.tracer._record(Span(self.name, self.ts_us, end - self.ts_us,
                                 self.depth, self.args))
        return False

    def set(self, **args) -> None:
        """Attach extra args after entry (e.g. counts known at exit)."""
        self.args = dict(self.args, **args)


class Tracer:
    """Ring-buffered span recorder; disabled (no-op) by default.

    ``annotate`` / ``annotate_step`` are the profiler sinks a live span
    enters (``jax.profiler.TraceAnnotation`` / ``StepTraceAnnotation``;
    they cost next to nothing while no profiler capture is running)."""

    annotate = staticmethod(jax.profiler.TraceAnnotation)
    annotate_step = staticmethod(jax.profiler.StepTraceAnnotation)

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 enabled: bool = False):
        self.capacity = capacity
        self.enabled = enabled
        self.events: deque[Span] = deque(maxlen=capacity)
        self.dropped = 0
        self._stack: list[str] = []

    # -- control -----------------------------------------------------------
    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        self.events.clear()
        self.dropped = 0
        self._stack.clear()

    # -- recording ---------------------------------------------------------
    def span(self, name: str, **args):
        if not self.enabled:
            return _NULL_SPAN_CTX
        return _SpanCtx(self, name, args, self.annotate(name))

    def step(self, name: str, step_num: int, **args):
        """A span that the profiler also shows as step ``step_num``."""
        if not self.enabled:
            return _NULL_SPAN_CTX
        return _SpanCtx(self, name, args,
                        self.annotate_step(name, step_num=step_num))

    def instant(self, name: str, **args) -> None:
        if not self.enabled:
            return
        self._record(Span(name, _now_us(), None, len(self._stack), args))

    def record(self, name: str, start_s: float, end_s: float, *, id: int,
               **args) -> None:
        """A finished span from two ``perf_counter`` stamps (seconds), kept
        at depth 0 under ``id``: such spans may overlap each other and the
        tick spans, so they are not nested."""
        if not self.enabled:
            return
        self._record(Span(name, start_s * 1e6, (end_s - start_s) * 1e6, 0,
                          args, id))

    def _record(self, span: Span) -> None:
        if len(self.events) == self.events.maxlen:
            self.dropped += 1
        self.events.append(span)

    # -- export ------------------------------------------------------------
    def chrome_trace(self, pid: int | None = None) -> dict:
        """``trace_event`` JSON object (the `{"traceEvents": [...]}` form)."""
        pid = os.getpid() if pid is None else pid
        tid = threading.get_ident() % 100000
        return {
            "traceEvents": [ev for s in self.events
                            for ev in s.to_events(pid, tid)],
            "displayTimeUnit": "ms",
            "otherData": {"dropped_spans": self.dropped},
        }

    def export_chrome(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    def spans(self, name: str | None = None) -> list[Span]:
        if name is None:
            return list(self.events)
        return [s for s in self.events if s.name == name]


NULL_TRACER = Tracer(capacity=1, enabled=False)
