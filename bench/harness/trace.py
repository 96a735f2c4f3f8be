"""From a profiler trace to numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
what the metrics read: for each chip the events of its "XLA Ops" line (one
per HLO instruction executed, named by the instruction) and "XLA Modules"
line (one per program run, e.g. ``jit_decode(...)``), and the host's
``bench_clock_sync`` marker that ties the trace's clock to the host's
``time.perf_counter``.  Times are nanoseconds on the trace's clock.

The reductions work on that compact form, which is also what the tests
keep as a recorded trace.
"""
from __future__ import annotations

import glob
import gzip
import json
from pathlib import Path

from harness.hlo import base_name

SYNC = "bench_clock_sync"
CONTAINERS = ("while", "conditional", "call")


def load(trace_dir: str) -> dict:
    import jax
    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    chips, sync = {}, None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    chip[key] = [(e.name, e.start_ns, e.end_ns)
                                 for e in line.events]
            chips[int(plane.name.rsplit(":", 1)[1])] = chip
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == SYNC:
                        sync = e.start_ns
    return {"chips": chips, "sync_ns": sync}


def save(reduced: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(reduced, f)


def read_saved(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    d["chips"] = {int(k): v for k, v in d["chips"].items()}
    return d


def window_ns(trace: dict, t_sync: float, t0: float, t1: float):
    """The host window [t0, t1] (perf_counter seconds) on the trace clock."""
    off = trace["sync_ns"] - t_sync * 1e9
    return t0 * 1e9 + off, t1 * 1e9 + off


def _clip(events, lo, hi):
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield name, s, e


def union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def is_container(name: str) -> bool:
    return base_name(name).startswith(CONTAINERS)


def busy_ns(chip: dict, lo: float, hi: float) -> float:
    return _length(union((s, e) for _, s, e in _clip(chip["ops"], lo, hi)))


def kernel_events(chip: dict, kernel: str, lo: float, hi: float) -> list:
    """(name, start, end) of one kernel's calls, by instruction name."""
    return [(n, s, e) for n, s, e in chip["ops"]
            if base_name(n) == kernel and s >= lo and e <= hi]


def top_ops(trace: dict, lo: float, hi: float, n: int = 10) -> list:
    """The device instructions that took the most time, by name, in seconds
    per chip (mean over chips); loops and calls that contain others left
    out."""
    totals: dict = {}
    chips = trace["chips"]
    for chip in chips.values():
        for name, s, e in _clip(chip["ops"], lo, hi):
            if not is_container(name):
                b = base_name(name)
                totals[b] = totals.get(b, 0.0) + (e - s)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / len(chips) / 1e9] for k, v in ranked]


def idle_gaps(trace: dict, lo: float, hi: float, host_spans: list,
              t_sync: float, n: int = 10) -> list:
    """The longest stretches with no instruction on chip 0, each named by
    the host span that covers most of it (``host_spans``: (name, start_s,
    end_s) on the perf_counter clock)."""
    off = trace["sync_ns"] - t_sync * 1e9
    spans = [(nm, s * 1e9 + off, e * 1e9 + off) for nm, s, e in host_spans]
    chip = trace["chips"][min(trace["chips"])]
    busy = union((s, e) for _, s, e in _clip(chip["ops"], lo, hi))
    gaps, prev = [], lo
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for gs, ge in gaps[:n]:
        best, cover = "host:no-span", 0.0
        for nm, s, e in spans:
            c = min(e, ge) - max(s, gs)
            if c > cover:
                best, cover = nm, c
        out.append([best, (ge - gs) / 1e9])
    return out
