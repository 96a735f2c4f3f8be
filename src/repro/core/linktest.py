"""PRBS link validation — the software analog of the paper's IBERT tests.

The paper programmed all four FPGAs with the Xilinx Integrated Bit Error
Ratio Tester and pushed 31-bit PRBS (pseudo-random binary sequence) payloads
over every inter-chip link, requiring stability at 10 Gbps.  Software cannot
see the serdes, but it can prove the *logical* link end-to-end: every mesh
axis must transport a PRBS payload bit-exactly through the collectives the
framework will actually use (all-gather, psum, ppermute, all-to-all).

``run_link_test(mesh)`` returns a per-axis ``LinkReport`` with a measured
bit-error count (must be 0) and an effective bandwidth probe.  The launcher
runs it in preflight (launch/preflight.py) before touching the model, the
same order the paper used (JTAG bring-up -> IBERT -> application).
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

# ---------------------------------------------------------------------------
# PRBS-31 generator (x^31 + x^28 + 1, the polynomial IBERT uses)
# ---------------------------------------------------------------------------

PRBS31_POLY = (31, 28)


def prbs31_bits(n_bits: int, seed: int = 0x7FFFFFFF) -> np.ndarray:
    """PRBS-31 bit stream via its linear recurrence b[n] = b[n-31]^b[n-28].

    Vectorized in chunks of 28 (the minimum lag), so generation is O(n/28)
    numpy ops.  Deterministic for a given seed, so both "ends" of a link
    can regenerate the expected sequence independently — exactly how IBERT
    checks BER.
    """
    assert seed != 0, "all-zero LFSR state is degenerate"
    bits = np.empty(n_bits + 31, np.uint8)
    for i in range(31):
        bits[i] = (seed >> (30 - i)) & 1
    n = 31
    total = n_bits + 31
    while n < total:
        m = min(28, total - n)
        bits[n:n + m] = bits[n - 31:n - 31 + m] ^ bits[n - 28:n - 28 + m]
        n += m
    return bits[31:]


def prbs31_words(n_words: int, seed: int = 0x7FFFFFFF) -> np.ndarray:
    bits = prbs31_bits(n_words * 32, seed)
    return np.packbits(bits.reshape(n_words, 32), axis=1, bitorder="big") \
        .view(">u4").astype(np.uint32).reshape(n_words)


def prbs31_payload(nbytes: int, seed: int = 0x7FFFFFFF) -> jnp.ndarray:
    words = prbs31_words((nbytes + 3) // 4, seed)
    return jnp.asarray(words, jnp.uint32)


# ---------------------------------------------------------------------------
# Per-axis link exercises
# ---------------------------------------------------------------------------


@dataclass
class LinkReport:
    axis: str
    size: int
    payload_bytes: int
    bit_errors: int
    checks: dict                     # collective name -> ok
    elapsed_s: float
    eff_bandwidth: float             # bytes/s through the axis (host-timed)

    @property
    def ok(self) -> bool:
        return self.bit_errors == 0 and all(self.checks.values())

    @property
    def bits_moved(self) -> int:
        """Bits the axis transported during the sweep (the BER denominator)."""
        return self.payload_bytes * 3 * self.size * 8

    @property
    def ber(self) -> float:
        """Measured bit-error ratio (0.0 for a clean sweep).  The serve
        engine's link gate (``ServeEngine.apply_link_reports``) thresholds
        this, so a clean link passes any threshold regardless of sweep
        length."""
        return self.bit_errors / max(self.bits_moved, 1)

    @property
    def ber_bound(self) -> float:
        """Upper bound the sweep can actually claim — IBERT convention: a
        zero-error run of N bits only proves BER < 1/N.  Reported in the
        burn-in table; tighten it with a longer payload."""
        return max(self.bit_errors, 1) / max(self.bits_moved, 1)


def _axis_exercises(payload: jax.Array, axis: str):
    """Runs inside shard_map (manual over ``axis``).  Each device holds the
    same PRBS payload; exercises the axis with the collectives the framework
    uses and returns bit-error counts per exercise."""
    p = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)

    # 1. all-gather: every device must receive every other device's payload
    #    bit-exactly (payload XOR'd with the sender index so corruption that
    #    swaps senders is also caught).
    stamped = payload ^ idx.astype(jnp.uint32)
    gathered = jax.lax.all_gather(stamped, axis)              # [p, n]
    expect = payload[None, :] ^ jnp.arange(p, dtype=jnp.uint32)[:, None]
    ag_errors = jnp.sum(
        jax.lax.population_count(gathered ^ expect).astype(jnp.uint32))

    # 2. ppermute ring: neighbour exchange (the paper's chip-to-chip nets).
    perm = [(i, (i + 1) % p) for i in range(p)]
    ring = jax.lax.ppermute(stamped, axis, perm)
    ring_expect = payload ^ ((idx - 1) % p).astype(jnp.uint32)
    pp_errors = jnp.sum(
        jax.lax.population_count(ring ^ ring_expect).astype(jnp.uint32))

    # 3. psum: reduction integrity (sum of known uint32 stamps, mod 2^32).
    s = jax.lax.psum(jnp.full((8,), idx + 1, jnp.uint32), axis)
    ps_errors = jnp.sum((s != p * (p + 1) // 2).astype(jnp.uint32))

    # 4. all_to_all: the MoE dispatch path.
    n = payload.shape[0] - (payload.shape[0] % p)
    chunks = stamped[:n].reshape(p, -1)
    exch = jax.lax.all_to_all(chunks, axis, split_axis=0, concat_axis=0,
                              tiled=False)
    # device d receives chunk[d] of every sender s: payload_chunk ^ s
    senders = jnp.arange(p, dtype=jnp.uint32)[:, None]
    exch_expect = payload[:n].reshape(p, -1)[idx][None, :] ^ senders
    a2a_errors = jnp.sum(
        jax.lax.population_count(exch ^ exch_expect).astype(jnp.uint32))

    # every device checks what *it* received; psum so no device's errors
    # are dropped when the replicated output is taken from device 0
    return tuple(jax.lax.psum(e, axis)
                 for e in (ag_errors, pp_errors, ps_errors, a2a_errors))


def run_link_test(mesh, payload_bytes: int = 1 << 16,
                  seed: int = 0x7FFFFFFF) -> list[LinkReport]:
    """IBERT-style validation of every mesh axis.  Returns per-axis reports;
    all must have .ok (bit_errors == 0) before training starts."""
    reports = []
    payload = prbs31_payload(payload_bytes, seed)
    for axis in mesh.axis_names:
        size = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
        # manual over EVERY axis (not just the one under test): the body
        # only issues collectives over ``axis``, so the semantics are the
        # same, and full-manual avoids the partial-manual PartitionId path
        # older XLA cannot partition
        fn = jax.shard_map(
            lambda x, a=axis: _axis_exercises(x, a),
            mesh=mesh, in_specs=P(), out_specs=P(),
            axis_names=set(mesh.axis_names), check_vma=False)
        t0 = time.perf_counter()
        ag, pp, ps, a2a = jax.jit(fn)(payload)
        ag, pp, ps, a2a = (int(jax.device_get(v)[0] if getattr(v, 'ndim', 0) else v)
                           for v in (ag, pp, ps, a2a))
        dt = time.perf_counter() - t0
        total = ag + pp + ps + a2a
        # bytes moved through the axis: AG gathers p payloads + ring + a2a
        moved = payload_bytes * (3 * size)
        reports.append(LinkReport(
            axis=axis, size=size, payload_bytes=payload_bytes,
            bit_errors=total,
            checks={"all_gather": ag == 0, "ppermute": pp == 0,
                    "psum": ps == 0, "all_to_all": a2a == 0},
            elapsed_s=dt, eff_bandwidth=moved / max(dt, 1e-9)))
    return reports


class LinkMonitor:
    """Continuous link monitoring: rolling per-axis BER/bandwidth windows.

    The paper's IBERT runs are not one-shot — the testers stay armed and
    the BER figure is a *running* ratio over everything transported.  This
    is the software analog: every sweep's :class:`LinkReport` is fed in
    (``record``), per-axis ``deque`` windows keep the last ``window``
    sweeps, and the rolling BER (total errors over total bits in window)
    plus mean effective bandwidth land in registry gauges.  ``derate``
    closes the loop: it feeds the rolling BERs into
    ``core.fabric.Fabric.with_link_ber`` so the planner's bandwidth model
    tracks observed link health, not the datasheet number.
    """

    def __init__(self, *, window: int = 8, registry=None):
        from repro.obs.metrics import NULL_REGISTRY
        self.window = window
        self._hist: dict[str, deque] = {}    # axis -> deque[LinkReport]
        reg = NULL_REGISTRY if registry is None else registry
        self._g_ber = reg.gauge(
            "link_ber", "rolling bit-error ratio per mesh axis",
            labels=("axis",))
        self._g_bw = reg.gauge(
            "link_bandwidth_bytes_per_s",
            "rolling mean effective bandwidth per mesh axis",
            labels=("axis",))
        self._c_sweeps = reg.counter("link_sweeps_total",
                                     "PRBS link sweeps recorded")
        self._c_errors = reg.counter("link_bit_errors_total",
                                     "bit errors observed across sweeps")

    def record(self, reports) -> dict[str, float]:
        """Fold a sweep's reports into the rolling windows; returns the
        updated per-axis rolling BER (the ``current_ber()`` view)."""
        for r in reports:
            ax = getattr(r, "axis", None)
            if ax is None:
                continue
            self._hist.setdefault(ax, deque(maxlen=self.window)).append(r)
            self._c_sweeps.inc()
            self._c_errors.inc(int(r.bit_errors))
            win = self._hist[ax]
            bits = sum(x.bits_moved for x in win)
            self._g_ber.labels(axis=ax).set(
                sum(x.bit_errors for x in win) / max(bits, 1))
            self._g_bw.labels(axis=ax).set(
                sum(x.eff_bandwidth for x in win) / len(win))
        return self.current_ber()

    def current_ber(self) -> dict[str, float]:
        out = {}
        for ax, win in sorted(self._hist.items()):
            bits = sum(x.bits_moved for x in win)
            out[ax] = sum(x.bit_errors for x in win) / max(bits, 1)
        return out

    def derate(self, fabric):
        """A fabric whose per-axis bandwidth reflects the rolling BER
        (retransmission overhead via ``Fabric.with_link_ber``)."""
        return fabric.with_link_ber(self.current_ber())

    def describe(self) -> str:
        if not self._hist:
            return "link monitor: no sweeps recorded"
        parts = [f"{ax}: ber={ber:.2e} ({len(self._hist[ax])} sweeps)"
                 for ax, ber in self.current_ber().items()]
        return "link monitor: " + ", ".join(parts)


def format_reports(reports: list[LinkReport]) -> str:
    """IBERT-style results table: one row per axis, with the BER bound the
    sweep length supports (a clean N-bit run proves BER < 1/N, no better)."""
    lines = [f"{'axis':8s} {'size':>4s} {'payload':>9s} {'bit-errors':>10s} "
             f"{'BER<':>9s} {'status':>7s}  checks"]
    for r in reports:
        status = "OK" if r.ok else "FAIL"
        checks = " ".join(f"{k}:{'ok' if v else 'ERR'}" for k, v in r.checks.items())
        lines.append(f"{r.axis:8s} {r.size:4d} {r.payload_bytes:9d} "
                     f"{r.bit_errors:10d} {r.ber_bound:9.1e} {status:>7s}  "
                     f"{checks}")
    return "\n".join(lines)
