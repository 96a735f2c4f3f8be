"""The reader of the program's compile instants, on spans written by
hand in the form ``run.py`` hands the readers (name, start, end, depth;
perf_counter seconds; an instant has end None)."""
from types import SimpleNamespace

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
from harness.spec import load_reader

from repro import obs

WINDOW = SimpleNamespace(t0=10.0, t_end=30.0)


def _ctx(spans):
    return {"window": WINDOW, "host_spans": spans}


def test_compiles_in_window_counts_instants_and_knows_an_older_program(
        monkeypatch):
    read = load_reader("compiles_in_window")
    ticks = [("tick", 11.0, 11.04, 0), ("collect", 11.0, 11.03, 1)]
    inside = [("compile", 12.0, None, 3), ("compile", 25.0, None, 0)]
    outside = [("compile", 9.0, None, 0), ("compile", 31.0, None, 0)]
    monkeypatch.setattr(obs, "watching_compiles", lambda: True)
    assert read(_ctx(ticks + inside + outside)) == 2.0
    assert read(_ctx(ticks + outside)) == 0.0
    # the listener not installed: no compile was marked, so no reading
    monkeypatch.setattr(obs, "watching_compiles", lambda: False)
    assert read(_ctx(ticks + inside)) is None
    # a program without the listener at all
    monkeypatch.delattr(obs, "watching_compiles")
    assert read(_ctx(ticks + inside)) is None
