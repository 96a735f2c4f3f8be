"""The timed window: a closed loop is primed before it opens (each first
request to its first decoded token), only what falls inside it is
counted, and the host-time reader leaves out the waits for the device."""
from types import SimpleNamespace

import pytest

import tiny
from harness import check, serve
from harness.spec import load_reader
from harness.traffic import Traffic

SEED = 2**32 + 5


@pytest.fixture(scope="module")
def window():
    c = tiny.cell()
    c.traffic["output_len"] = {"dist": "uniform", "min": 24, "max": 48}
    c.traffic["engine"]["capacity"] = 128
    counter = serve.CompileCounter()
    _, eng = serve.build(c, SEED)
    vocab = int(c.config["vocab_size"])
    serve.warm(eng, c.traffic, vocab)
    try:
        w = serve.run_window(eng, Traffic(c.traffic, vocab, SEED), 1.0,
                             counter)
    finally:
        counter.close()
    return c, w


def test_closed_loop_is_primed_before_the_window(window):
    c, w = window
    first = w.recs[:c.traffic["clients"]]
    assert all(rc.due < w.t0 and rc.req.first_token_at < w.t0
               and rc.req.token_times[0] < w.t0 for rc in first)
    assert all(rc.due >= w.t0 for rc in w.recs[len(first):])
    assert w.tick_contexts and all(
        len(ctx) == c.traffic["clients"] for ctx in w.tick_contexts[:1])


def test_only_what_falls_inside_the_window_counts(window):
    _, w = window
    e2e = serve.end_to_end(w, 1.0)
    inside = before = 0
    for rc in w.recs:
        for t in [rc.req.first_token_at, *rc.req.token_times]:
            if t and w.t0 <= t <= w.t_end:
                inside += 1
            elif t and t < w.t0:
                before += 1
    assert before >= 1 and e2e["_tokens"] == inside
    assert e2e["output_tokens_per_s"] == inside / 1.0
    assert 0 < e2e["itl_p99_s"] < 1.0


def test_sample_holds_the_longest_and_every_slot_at_the_close(window):
    c, w = window
    assert len(w.held) == c.traffic["clients"]
    reqs = [rc.req for rc in w.recs]
    picked = check.sample(reqs, SEED, 1, w.held)
    longest = max(reqs, key=lambda r: len(r.generated))
    assert picked[0] is longest and len(picked) <= len(w.held) + 1
    assert all(any(p is r for p in picked) for r in w.held)
    more = check.sample(reqs, SEED, 10**6, w.held)
    assert len(more) == sum(1 for r in reqs if r.generated)


def test_engine_host_time_leaves_out_waits_and_admissions():
    read = load_reader("engine_host_ms_per_tick")
    spans = [
        # tick 1: 10 ms, of which collect waits 8 ms -> 2 ms of host time
        ("tick", 1.000, 1.010, 0), ("dispatch", 1.000, 1.001, 1),
        ("collect", 1.001, 1.009, 1), ("admit", 1.009, 1.010, 1),
        # tick 2: 12 ms, collect 6 ms -> 6 ms
        ("tick", 1.010, 1.022, 0), ("collect", 1.012, 1.018, 1),
        # tick 3 admits a request: its admit waits for a prefill; left out
        ("tick", 1.022, 1.300, 0), ("collect", 1.023, 1.030, 1),
        ("admit", 1.030, 1.300, 1), ("req:admit", 1.299, None, 2),
        # outside the window
        ("tick", 3.000, 3.500, 0),
    ]
    ctx = {"window": SimpleNamespace(t0=1.0, t_end=2.0), "host_spans": spans}
    assert read(ctx) == pytest.approx(4.0)
    ctx["host_spans"] = spans[-1:]
    assert read(ctx) is None
