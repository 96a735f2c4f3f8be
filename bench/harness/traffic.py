"""One general generator for every traffic mix: a mix is a data file.

A mix (``bench/traffic/<mix>.json``) states:

* ``loop``: ``"closed"`` (``clients`` callers, each sending its next request
  the moment the previous reply completes, plus ``think_s``) or ``"open"``
  (arrivals at ``rate`` requests/s; ``burst_cv`` > 1 makes the gaps
  gamma-distributed with that coefficient of variation, 1 is Poisson);
* ``prompt_len`` / ``output_len``: ``{"dist": "uniform"|"lognormal",
  "min", "max"[, "median", "sigma"]}``;
* ``strata``: how many lengths make one cycle.  The lengths are the
  distribution's quantiles at (k + 1/2) / strata, each cycle in an order
  that is the same for every seed, as are an open loop's arrivals: a
  window sees a few requests of a cycle, and which ones decides its work
  (how many end and are admitted inside it), so the seed must not.  The
  seed draws the token ids;
* ``engine``: the serving engine's settings (slots, capacity, kv layout,
  max_admit);
* ``check``: how many served tokens the correctness sample holds.

Request ``i`` of a seed is the same on every run of that seed, and has
the same sizes under every seed.
"""
from __future__ import annotations

import math
import statistics
from dataclasses import dataclass

import numpy as np


@dataclass
class Draw:
    index: int
    prompt: np.ndarray
    max_new: int


def quantile_lengths(dist: dict, n: int) -> list[int]:
    lo, hi = int(dist["min"]), int(dist["max"])
    qs = [(k + 0.5) / n for k in range(n)]
    if dist["dist"] == "uniform":
        xs = [lo + q * (hi - lo) for q in qs]
    elif dist["dist"] == "lognormal":
        nd = statistics.NormalDist(math.log(dist["median"]), dist["sigma"])
        xs = [math.exp(nd.inv_cdf(q)) for q in qs]
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return [min(hi, max(lo, int(round(x)))) for x in xs]


def _rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *tags])


ORDER = 0       # the key of the sizes' order and of the arrivals, any seed


class Traffic:
    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix, self.vocab, self.seed = mix, vocab, seed
        self.n = int(mix["strata"])
        self.prompt_lens = quantile_lengths(mix["prompt_len"], self.n)
        self.output_lens = quantile_lengths(mix["output_len"], self.n)
        eng = mix["engine"]
        if max(self.prompt_lens) + max(self.output_lens) > eng["capacity"]:
            raise ValueError("longest prompt + longest output exceeds the "
                             "engine's capacity")
        self._perms: dict = {}

    @property
    def closed(self) -> bool:
        return self.mix["loop"] == "closed"

    def _perm(self, cycle: int, which: int) -> np.ndarray:
        key = (cycle, which)
        if key not in self._perms:
            self._perms[key] = _rng(ORDER, 1, cycle, which).permutation(
                self.n)
        return self._perms[key]

    def request(self, i: int) -> Draw:
        cycle, k = divmod(i, self.n)
        p = self.prompt_lens[self._perm(cycle, 0)[k]]
        o = self.output_lens[self._perm(cycle, 1)[k]]
        toks = _rng(self.seed, 2, i).integers(0, self.vocab, p,
                                              dtype=np.int32)
        return Draw(i, toks, o)

    def arrivals(self, seconds: float) -> list[float]:
        """Open loop: due offsets (s) from the window's start."""
        rate = float(self.mix["rate"])
        cv = float(self.mix.get("burst_cv", 1.0))
        shape = 1.0 / (cv * cv)
        rng = _rng(ORDER, 3)
        out, t = [], 0.0
        while True:
            t += rng.gamma(shape, 1.0 / (rate * shape))
            if t >= seconds:
                return out
            out.append(t)


def bucket(n: int, capacity: int) -> int:
    """The padded length a prompt of ``n`` tokens is prefilled at: the next
    power of two >= 8, capped at the capacity (the dense engine's rule)."""
    b = 8
    while b < n:
        b *= 2
    return min(b, capacity)


def warm_shapes(mix: dict) -> list[tuple[int, int, int]]:
    """(bucket, requests, prompt length) for every prefill the mix can
    cause: each bucket its prompt lengths reach, at every padded row count
    (the powers of two up to max_admit rounded up; ``requests`` admitted
    together pad to that count)."""
    eng = mix["engine"]
    cap = eng["capacity"]
    lo, hi = int(mix["prompt_len"]["min"]), int(mix["prompt_len"]["max"])
    reps = {}
    n = lo
    while n <= hi:
        b = bucket(n, cap)
        reps.setdefault(b, n)
        n = b + 1
    admit = min(eng.get("max_admit", eng["slots"]), eng["slots"])
    rows = [1 << k for k in range((admit - 1).bit_length() + 1)]
    return [(b, min(r, admit), reps[b]) for b in sorted(reps) for r in rows]
