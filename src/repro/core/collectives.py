"""Topology-aware collectives: keep bulk traffic on the fast tier.

Paper analog: the ExaNoDe MCM routes high-density traffic over intra-MCM
LVDS and lets only aggregated traffic cross the 10 Gbps SFP+ links.  The
TPU-native translation:

* ``hierarchical_psum``   — 2-level all-reduce: reduce-scatter on the fast
  (ICI) axes, all-reduce of the 1/P shard across the slow (pod) axis,
  all-gather back on ICI.  Cross-pod bytes drop from B to B/P_fast.
* ``pod_manual``          — partial-manual shard_map: the 'pod' axis is
  manual (we place its collectives by hand, optionally int8-compressed via
  core/compression.py) while 'data'/'model' stay automatic, so the model's
  pjit-style sharding annotations keep working inside.
* ``sync_grads_over_pod`` — the gradient synchronization used by the
  multi-pod train step: pmean over 'pod', either exact or compressed with
  error feedback.

All functions are jit-safe and mesh-agnostic (axis names are parameters).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import compression


def axis_index_of(axis: str) -> jax.Array:
    return jax.lax.axis_index(axis)


# ---------------------------------------------------------------------------
# Hierarchical all-reduce (full-manual building block)
# ---------------------------------------------------------------------------


def hierarchical_psum(x: jax.Array, fast_axis: str, slow_axis: str) -> jax.Array:
    """All-reduce over (fast_axis × slow_axis) that crosses the slow tier
    with only 1/P_fast of the bytes.

    reduce-scatter(fast) -> psum(slow) on the shard -> all-gather(fast).
    Must run inside a shard_map where both axes are manual.  The leading dim
    of ``x`` must be divisible by the fast-axis size.
    """
    p_fast = jax.lax.axis_size(fast_axis)
    lead = x.shape[0]
    assert lead % p_fast == 0, (lead, p_fast)
    shard = jax.lax.psum_scatter(x, fast_axis, scatter_dimension=0, tiled=True)
    shard = jax.lax.psum(shard, slow_axis)
    return jax.lax.all_gather(shard, fast_axis, axis=0, tiled=True)


def flat_psum(x: jax.Array, axes: Sequence[str]) -> jax.Array:
    """Single flat all-reduce over all ``axes`` (the baseline the paper's
    tiered design improves on: every byte crosses the slowest link)."""
    return jax.lax.psum(x, tuple(axes))


# ---------------------------------------------------------------------------
# Partial-manual pod region
# ---------------------------------------------------------------------------


def pod_manual(fn: Callable, mesh, in_specs, out_specs,
               pod_axis: str = "pod") -> Callable:
    """shard_map manual over only the pod axis; intra-pod axes stay auto.

    ``in_specs``/``out_specs`` mention only the pod axis (P() = replicated
    across pods, P('pod') = split).  Inside ``fn`` the model's
    with_sharding_constraint annotations over 'data'/'model' keep working.
    """
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names={pod_axis},
                         check_vma=False)


def sync_grads_over_pod(grads, *, pod_axis: str = "pod",
                        compress: bool = False, residual=None):
    """pmean gradients across pods (must run inside a pod-manual region).

    compress=False: exact bf16->f32 pmean (one all-reduce per leaf across
    the slow tier, full bytes).
    compress=True: int8 block-quantized payload with error feedback
    (residual pytree threaded through the train state); cross-pod bytes
    drop ~4x.  Returns (synced_grads, new_residual).
    """
    npods = jax.lax.axis_size(pod_axis)
    if not compress:
        synced = jax.tree.map(
            lambda g: jax.lax.psum(g, pod_axis) / npods, grads)
        return synced, residual
    assert residual is not None, "compressed sync needs an error-feedback state"
    sent, new_residual = compression.ef_compress(grads, residual)
    synced = jax.tree.map(
        lambda s: jax.lax.psum(s, pod_axis) / npods, sent)
    return synced, new_residual


# ---------------------------------------------------------------------------
# Collective cost model (napkin math used by the planner & benchmarks)
# ---------------------------------------------------------------------------


def ring_all_reduce_bytes(nbytes: float, p: int) -> float:
    """Per-device bytes crossing links for a ring all-reduce."""
    return 2.0 * nbytes * (p - 1) / p


def ring_all_gather_bytes(nbytes_out: float, p: int) -> float:
    return nbytes_out * (p - 1) / p


def ring_reduce_scatter_bytes(nbytes_in: float, p: int) -> float:
    return nbytes_in * (p - 1) / p


def all_to_all_bytes(nbytes: float, p: int) -> float:
    return nbytes * (p - 1) / p


def hierarchical_all_reduce_time(nbytes: float, p_fast: int, p_slow: int,
                                 bw_fast: float, bw_slow: float,
                                 compress_slow: bool = False) -> float:
    """Model time for RS(fast) + AR(slow, maybe int8) + AG(fast)."""
    t_rs = ring_reduce_scatter_bytes(nbytes, p_fast) / bw_fast
    slow_bytes = nbytes / p_fast
    if compress_slow:
        slow_bytes = compression.compressed_bytes(slow_bytes)
    t_ar = ring_all_reduce_bytes(slow_bytes, p_slow) / bw_slow
    t_ag = ring_all_gather_bytes(nbytes, p_fast) / bw_fast
    return t_rs + t_ar + t_ag


def flat_all_reduce_time(nbytes: float, p_total: int, bw_slowest: float) -> float:
    return ring_all_reduce_bytes(nbytes, p_total) / bw_slowest
