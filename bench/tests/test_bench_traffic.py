"""The one traffic generator: repeatable per seed, the same sizes in the
same order for every seed, and a warm-up set that covers every shape a
mix can cause."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

import tiny
from harness.traffic import Traffic, bucket, quantile_lengths, warm_shapes

# every mix file, and a chat-shaped mix with log-normal lengths and more
# clients than ``max_admit``, so that both length laws are covered
CHAT = {"loop": "closed", "clients": 16, "think_s": 0.0,
        "prompt_len": {"dist": "lognormal", "median": 384, "sigma": 0.7,
                       "min": 128, "max": 1024},
        "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                       "min": 32, "max": 512},
        "strata": 64, "engine": {"slots": 16, "capacity": 2048,
                                 "kv_layout": "dense", "max_admit": 4},
        "check": {"min_tokens": 2048}}
MIXES = {p.stem: p.read_text()
         for p in sorted((tiny.BENCH / "traffic").glob("*.json"))}
MIXES["lognormal-chat"] = json.dumps(CHAT)
SEEDS = (0, 2**31 + 11, 2**40 + 3)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_mix_repeats_exactly_per_seed(name):
    mix = json.loads(MIXES[name])
    for seed in SEEDS:
        a, b = Traffic(mix, 151936, seed), Traffic(mix, 151936, seed)
        for i in (0, 5, 70):
            da, db = a.request(i), b.request(i)
            assert da.max_new == db.max_new
            np.testing.assert_array_equal(da.prompt, db.prompt)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_every_seed_serves_the_same_sizes(name):
    mix = json.loads(MIXES[name])
    n = mix["strata"]
    sizes = []
    for seed in SEEDS:
        t = Traffic(mix, 151936, seed)
        draws = [t.request(i) for i in range(n)]
        sizes.append((Counter(len(d.prompt) for d in draws),
                      Counter(d.max_new for d in draws)))
        for d in draws:
            assert mix["prompt_len"]["min"] <= len(d.prompt) <= \
                mix["prompt_len"]["max"]
            assert mix["output_len"]["min"] <= d.max_new <= \
                mix["output_len"]["max"]
            assert len(d.prompt) + d.max_new <= mix["engine"]["capacity"]
    assert sizes[0] == sizes[1] == sizes[2]
    # in the same order: the seed draws the token ids, not the work
    draws = [[Traffic(mix, 151936, s).request(i) for i in range(n)]
             for s in SEEDS]
    orders = [[(len(d.prompt), d.max_new) for d in ds] for ds in draws]
    assert orders[0] == orders[1] == orders[2]
    assert len(set(orders[0])) > n // 2
    assert not np.array_equal(draws[0][0].prompt, draws[1][0].prompt)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_warm_shapes_cover_every_prefill(name):
    mix = json.loads(MIXES[name])
    eng = mix["engine"]
    shapes = warm_shapes(mix)
    buckets = {b for b, _, _ in shapes}
    t = Traffic(mix, 151936, 7)
    for i in range(3 * mix["strata"]):
        assert bucket(len(t.request(i).prompt), eng["capacity"]) in buckets
    for b, count, length in shapes:
        assert bucket(length, eng["capacity"]) == b
        assert 1 <= count <= eng["max_admit"]
    rows = {1 << (c - 1).bit_length() for _, c, _ in shapes}
    assert rows == {1, 2, 4}


def test_lengths_are_quantiles():
    u = quantile_lengths({"dist": "uniform", "min": 0, "max": 100}, 4)
    assert u == [12, 38, 62, 88]
    ln = quantile_lengths({"dist": "lognormal", "median": 100, "sigma": 0.5,
                           "min": 1, "max": 10**6}, 3)
    assert ln[1] == 100 and ln[0] < 100 < ln[2]


def test_clipped_lognormal_lengths_are_its_quantiles():
    """Log-normal lengths clipped at both ends: the strata's quantiles,
    sorted, the clips reached at both tails, the median between the two
    middle strata."""
    dist = {"dist": "lognormal", "median": 384, "sigma": 0.7,
            "min": 128, "max": 1024}
    p = quantile_lengths(dist, 64)
    assert len(p) == 64 and p == sorted(p)
    assert p[:4] == [128] * 4 and p[-5:] == [1024] * 5
    assert p[31] < 384 < p[32]


def test_open_loop_arrivals_repeat():
    mix = dict(tiny.MIX, loop="open", rate=20.0, burst_cv=2.0)
    a = Traffic(mix, 256, 9).arrivals(10.0)
    assert a == Traffic(mix, 256, 9).arrivals(10.0)
    assert a == Traffic(mix, 256, 2**40 + 9).arrivals(10.0)
    assert 100 < len(a) < 300 and all(0 < x < 10 for x in a)
