"""The admission readers, on spans written by hand in the form ``run.py``
hands the readers (name, start, end, depth; perf_counter seconds)."""
from types import SimpleNamespace

import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
from harness.spec import load_reader

WINDOW = SimpleNamespace(t0=10.0, t_end=30.0)


def _ctx(spans):
    return {"window": WINDOW, "host_spans": spans}


def test_queue_wait_p90_reads_waits_that_end_in_the_window():
    read = load_reader("queue_wait_p90_s")
    inside = [("req:queued", 11.0 - w, 11.0, 0)
              for w in (0.0, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0)]
    outside = [("req:queued", 5.0, 9.0, 0), ("req:queued", 29.0, 31.0, 0)]
    other = [("admit:wait", 12.0, 19.0, 2), ("tick", 10.0, 20.0, 0)]
    assert read(_ctx(inside + outside + other)) == pytest.approx(0.9)
    assert read(_ctx(outside + other)) is None


def test_admit_wait_is_the_mean_wait_that_starts_in_the_window():
    read = load_reader("admit_wait_ms")
    spans = [("admit", 11.0, 11.05, 1), ("admit:prefill", 11.0, 11.01, 2),
             ("admit:wait", 11.01, 11.05, 2), ("admit:wait", 20.0, 20.02, 2),
             ("admit:wait", 9.99, 10.5, 2), ("admit:wait", 29.99, 31.0, 2),
             ("req:queued", 10.0, 11.0, 0)]
    assert read(_ctx(spans)) == pytest.approx((40 + 20 + 1010) / 3)
    assert read(_ctx(spans[-1:])) is None
