"""shard_map kernel dispatch: each Pallas kernel's logical axes mapped onto
the model mesh.

The paper's MCM is two fabric tiers — chip-to-chip links inside a module,
10 Gbps SFP+ between modules — and the mesh axes ("pod"/"data"/"model")
mirror that.  But a Pallas call inside an auto-pjit region is a black box
to the partitioner: it replicates the kernel operands over the 'model' axis
and runs the full-size kernel on every device.  This module makes the
partitioning explicit — the ExaNeSt lesson that the win comes from putting
the mapping in the programming model, not from hoping a global compiler
discovers it.  Each wrapper slices the kernel's *logical* axes over mesh
axes via the activation-rules context (models/sharding.py) and emits only
the unavoidable collectives:

  flash_attention  — Q/KV heads over 'model', batch over the DP axes.  The
                     per-head math is untouched (online softmax never
                     crosses heads), so forward, dq and dkv kernels all run
                     shard-local with NO collectives; the psum for the
                     head-summed output projection stays with the einsum
                     outside (Megatron).  Forward AND both custom-VJP
                     backward kernels run per-shard — the wrapper carries
                     its own ``jax.custom_vjp`` so autodiff never has to
                     transpose through the shard_map region.
  swiglu_ffn       — FFN columns (d_ff) over 'model' (column-parallel
                     wi_gate/wi_up, row-parallel wo), token rows over the
                     DP axes.  Forward partial outputs and backward dx are
                     psum'd over 'model'; weight grads are psum'd over the
                     row (DP) axes — the two unavoidable collectives.
  decode_attention — cache rows (serve slots) over the DP axes, KV heads
                     over 'model' where they divide.  Per-(row, kv-head)
                     math is untouched, so sharded outputs are *bitwise*
                     equal to replicated ones; the per-token [B,H,D] head
                     all_gather before the output projection is the only
                     collective (negligible next to the cache stream the
                     sharding divides by the axis size).
  paged_decode_attention — block-table rows over the DP axes, the pooled
                     KV heads over 'model'; same structure as the dense
                     decode kernel.

Fallback contract: with ``mesh=None``, with the knob off, or when a
divisibility gate fails (heads % model-axis != 0, d_ff % model-axis != 0,
per-shard block divisibility), every wrapper calls the plain ``ops``
entry point with identical arguments — bitwise today's replicated path.
``REPRO_KERNEL_PARTITION`` (auto|off) overrides the ``kernel_partition``
rule and fails fast on unknown values like the other kernel knobs.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Optional

import jax
from jax.sharding import PartitionSpec as P

from repro.kernels import decode_attention as _da
from repro.kernels import flash_attention as _fa
from repro.kernels import fused_ffn as _ffn
from repro.kernels import ops
from repro.kernels import paged_attention as _pa
from repro.models.sharding import current_rules

PARTITION_CHOICES = ("auto", "off")


def axis_shardable(dim: int, tp: int) -> bool:
    """THE divisibility law for sharded kernel dispatch: a logical axis of
    size ``dim`` partitions over a mesh axis of size ``tp`` iff it divides.
    The dispatch gate (``_model_axis``), the describe report and the
    registry ``Capabilities.*_shardable`` predicates all call this one
    function so they can never drift."""
    return tp > 1 and dim > 0 and dim % tp == 0


def resolve_kernel_partition(knob: str = "auto") -> str:
    """``auto`` shards every kernel whose gates pass; ``off`` forces the
    replicated dispatch (the benchmark baseline).  ``REPRO_KERNEL_PARTITION``
    overrides and fails fast on unknown values (the shared env contract)."""
    env = os.environ.get("REPRO_KERNEL_PARTITION", "").strip().lower()
    if env:
        if env not in PARTITION_CHOICES:
            raise ValueError(
                f"REPRO_KERNEL_PARTITION={env!r} is not a valid kernel "
                f"partition mode; valid choices: "
                f"{', '.join(PARTITION_CHOICES)}")
        knob = env
    if knob not in PARTITION_CHOICES:
        raise ValueError(
            f"unknown kernel partition mode {knob!r}; valid choices: "
            f"{', '.join(PARTITION_CHOICES)}")
    return knob


# ---------------------------------------------------------------------------
# Partition-context resolution (activation rules -> mesh axes)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelPartition:
    """One kernel call's mesh mapping: hashable so the custom_vjp wrappers
    can carry it as a nondiff argument (jit caches on it)."""

    mesh: Any                            # jax.sharding.Mesh (hashable)
    model: Optional[str]                 # mesh axis for the sharded logical
    batch: Optional[tuple]               # DP axes for the row/batch dim

    @property
    def batch_spec(self):
        if not self.batch:
            return None
        return self.batch[0] if len(self.batch) == 1 else self.batch

    def tp(self) -> int:
        return _axis_size(self.mesh, self.model)

    def dp(self) -> int:
        out = 1
        for a in self.batch or ():
            out *= _axis_size(self.mesh, a)
        return out


def _axis_size(mesh, axis) -> int:
    return 1 if axis is None else mesh.shape[axis]


def _active_mesh(rules: dict):
    """The mesh to partition over, or None (replicated fallback)."""
    mesh = rules.get("mesh")
    if mesh is None:
        return None
    if resolve_kernel_partition(rules.get("kernel_partition", "auto")) == "off":
        return None
    return mesh


def _batch_axes(rules: dict, mesh, rows: int) -> Optional[tuple]:
    """DP axes for the leading row/batch dim, dropped (None) whenever the
    row count does not divide — partial row shards are never worth the
    ragged bookkeeping at kernel granularity."""
    b = rules.get("batch")
    if b is None:
        return None
    axes = (b,) if isinstance(b, str) else tuple(b)
    axes = tuple(a for a in axes if a in mesh.axis_names)
    dp = 1
    for a in axes:
        dp *= _axis_size(mesh, a)
    if dp <= 1 or rows % dp != 0:
        return None
    return axes


def _model_axis(rules: dict, rule: str, mesh, dim: int) -> Optional[str]:
    """The mesh axis the given logical-axis rule names, when the dimension
    divides it; None otherwise (the head/column-divisibility gate)."""
    axis = rules.get(rule)
    if axis is None or not isinstance(axis, str) or axis not in mesh.axis_names:
        return None
    if not axis_shardable(dim, _axis_size(mesh, axis)):
        return None
    return axis


def _interpret() -> bool:
    return ops._interpret()


# ---------------------------------------------------------------------------
# Flash attention (train/prefill): heads over 'model', batch over DP axes
# ---------------------------------------------------------------------------


def _flash_fwd_sharded(q, k, v, causal, window, part: KernelPartition):
    B, H, S, D = q.shape
    T = k.shape[2]
    bq, bk = min(_fa.DEFAULT_BQ, S), min(_fa.DEFAULT_BK, T)
    spec = P(part.batch_spec, part.model, None, None)
    lse_spec = P(part.batch_spec, part.model, None, None)
    body = lambda q, k, v: _fa._forward(q, k, v, causal, window, bq, bk,
                                        _interpret())
    out, lse = jax.shard_map(
        body, mesh=part.mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, lse_spec), check_vma=False)(q, k, v)
    return out, (q, k, v, out, lse)


def _flash_bwd_sharded(causal, window, part: KernelPartition, res, g):
    q, k, v, out, lse = res
    B, H, S, D = q.shape
    T = k.shape[2]
    bq, bk = min(_fa.DEFAULT_BQ, S), min(_fa.DEFAULT_BK, T)
    spec = P(part.batch_spec, part.model, None, None)
    lse_spec = P(part.batch_spec, part.model, None, None)
    body = lambda q, k, v, o, lse, g: _fa._backward(
        q, k, v, o, lse, g, causal, window, bq, bk, _interpret())
    # every operand is head-sharded, so dq/dk/dv are shard-local: the psum
    # for the GQA repeat / projection weights happens outside with autodiff
    return jax.shard_map(
        body, mesh=part.mesh,
        in_specs=(spec, spec, spec, spec, lse_spec, spec),
        out_specs=(spec, spec, spec), check_vma=False)(q, k, v, out, lse, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_sharded(q, k, v, causal, window, part):
    return _flash_fwd_sharded(q, k, v, causal, window, part)[0]


_flash_sharded.defvjp(_flash_fwd_sharded, _flash_bwd_sharded)


def _flash_blocks_ok(S: int, T: int) -> bool:
    """Mirror of ``ops.flash_attention``'s grid assertion (and of
    models.attention.flash_train_supported's shape gate): both sequence
    axes must split into equal blocks.  Head sharding never changes S/T,
    so an ineligible shape falls back to the replicated call, which fails
    loudly instead of truncating the grid."""
    return ((S <= _fa.DEFAULT_BQ or S % _fa.DEFAULT_BQ == 0)
            and (T <= _fa.DEFAULT_BK or T % _fa.DEFAULT_BK == 0))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0) -> jax.Array:
    """q/k/v [B,H,S|T,D] -> [B,H,S,D]; differentiable.  Head-sharded over
    the 'model' axis (``heads_act`` rule) when H divides it; replicated
    ``ops.flash_attention`` otherwise — per-head math is identical either
    way, so the fallback is exact, not approximate."""
    rules = current_rules() or {}
    mesh = _active_mesh(rules)
    if mesh is not None and _flash_blocks_ok(q.shape[2], k.shape[2]):
        model = _model_axis(rules, "heads_act", mesh, q.shape[1])
        if model is not None:
            part = KernelPartition(mesh, model,
                                   _batch_axes(rules, mesh, q.shape[0]))
            return _flash_sharded(q, k, v, causal, window, part)
    return ops.flash_attention(q, k, v, causal=causal, window=window)


# ---------------------------------------------------------------------------
# Fused SwiGLU FFN: columns over 'model', token rows over DP axes
# ---------------------------------------------------------------------------


def _swiglu_fwd_sharded(x, wg, wu, wd, part: KernelPartition):
    N, D = x.shape
    F = wg.shape[1]
    n_loc, f_loc = N // part.dp(), F // part.tp()
    br = _ffn.pick_block(n_loc, _ffn.DEFAULT_BR, 8)
    bf = _ffn.pick_block(f_loc, _ffn.DEFAULT_BF)

    def body(x, wg, wu, wd):
        y = _ffn._forward(x, wg, wu, wd, br, bf, _interpret())
        return jax.lax.psum(y, part.model)     # row-parallel partial outputs

    y = jax.shard_map(
        body, mesh=part.mesh,
        in_specs=(P(part.batch_spec, None), P(None, part.model),
                  P(None, part.model), P(part.model, None)),
        out_specs=P(part.batch_spec, None), check_vma=False)(x, wg, wu, wd)
    return y, (x, wg, wu, wd)


def _swiglu_bwd_sharded(part: KernelPartition, res, dy):
    x, wg, wu, wd = res
    N, D = x.shape
    F = wg.shape[1]
    n_loc, f_loc = N // part.dp(), F // part.tp()
    br = _ffn.pick_block(n_loc, _ffn.DEFAULT_BR, 8)
    bf = _ffn.pick_block(f_loc, _ffn.DEFAULT_BF)

    def body(x, wg, wu, wd, dy):
        dx, dwg, dwu, dwd = _ffn._backward(x, wg, wu, wd, dy, br, bf,
                                           _interpret())
        dx = jax.lax.psum(dx, part.model)      # column-partial dX
        if part.batch:                         # row-partial weight grads
            dwg, dwu, dwd = (jax.lax.psum(t, part.batch)
                             for t in (dwg, dwu, dwd))
        return dx, dwg, dwu, dwd

    return jax.shard_map(
        body, mesh=part.mesh,
        in_specs=(P(part.batch_spec, None), P(None, part.model),
                  P(None, part.model), P(part.model, None),
                  P(part.batch_spec, None)),
        out_specs=(P(part.batch_spec, None), P(None, part.model),
                   P(None, part.model), P(part.model, None)),
        check_vma=False)(x, wg, wu, wd, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _swiglu_sharded(x, wg, wu, wd, part):
    return _swiglu_fwd_sharded(x, wg, wu, wd, part)[0]


_swiglu_sharded.defvjp(_swiglu_fwd_sharded, _swiglu_bwd_sharded)


def swiglu_ffn(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
               w_down: jax.Array) -> jax.Array:
    """x [N,D] -> [N,D]; differentiable.  Column-sharded over the 'model'
    axis (``mlp_act`` rule) when d_ff divides it and the per-shard grid
    still blocks evenly; replicated ``ops.swiglu_ffn`` otherwise."""
    rules = current_rules() or {}
    mesh = _active_mesh(rules)
    if mesh is not None:
        F = w_gate.shape[1]
        model = _model_axis(rules, "mlp_act", mesh, F)
        if model is not None:
            part = KernelPartition(mesh, model,
                                   _batch_axes(rules, mesh, x.shape[0]))
            if _ffn.blocks_ok(x.shape[0] // part.dp(), F // part.tp()):
                return _swiglu_sharded(x, w_gate, w_up, w_down, part)
    return ops.swiglu_ffn(x, w_gate, w_up, w_down)


# ---------------------------------------------------------------------------
# Decode kernels: cache/block-table rows over DP axes, KV heads over 'model'
# ---------------------------------------------------------------------------


def _decode_partition(rules, mesh, B: int, KV: int) -> Optional[KernelPartition]:
    """Rows over the DP axes + KV heads over the model axis where each
    divides; None when neither does (replicated fallback)."""
    model = _model_axis(rules, "heads_act", mesh, KV)
    batch = _batch_axes(rules, mesh, B)
    if model is None and batch is None:
        return None
    return KernelPartition(mesh, model, batch)


def _gather_heads(out, part: KernelPartition):
    """Per-token [B_loc, H_loc, D] -> [B_loc, H, D]: the decode path's one
    collective.  Gathering (instead of head-sharding the output projection)
    keeps the post-kernel program identical to the replicated path, so
    sharded and replicated decode token streams stay bitwise-comparable."""
    if part.model is None:
        return out
    return jax.lax.all_gather(out, part.model, axis=1, tiled=True)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_pos: jax.Array, pos: jax.Array, *,
                     window: int = 0) -> jax.Array:
    """Flash-decode with the KV cache sharded: rows [B] over the DP axes,
    KV heads over 'model' when they divide (q [B,H,D]; caches [B,T,KV,D]).
    Per-(row, kv-head) math is untouched -> bitwise equal to the
    replicated kernel."""
    rules = current_rules() or {}
    mesh = _active_mesh(rules)
    if mesh is not None:
        part = _decode_partition(rules, mesh, q.shape[0], k.shape[2])
        if part is not None:
            def body(q, k, v, kv_pos, pos):
                out = _da.decode_attention(q, k, v, kv_pos, pos,
                                           window=window,
                                           interpret=_interpret())
                return _gather_heads(out, part)

            b, m = part.batch_spec, part.model
            return jax.shard_map(
                body, mesh=part.mesh,
                in_specs=(P(b, m, None), P(b, None, m, None),
                          P(b, None, m, None), P(b, None), P(b)),
                out_specs=P(b, None, None), check_vma=False)(
                q, k, v, kv_pos, pos)
    return ops.decode_attention(q, k, v, kv_pos, pos, window=window)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, pos_pool: jax.Array,
                           block_table: jax.Array,
                           pos: jax.Array) -> jax.Array:
    """Paged decode with block-table rows [B] over the DP axes and the
    pooled KV heads over 'model' when they divide (pools [N,bs,KV,D] are
    row-replicated — every slot gathers from the shared pool)."""
    rules = current_rules() or {}
    mesh = _active_mesh(rules)
    if mesh is not None:
        part = _decode_partition(rules, mesh, q.shape[0], k_pool.shape[2])
        if part is not None:
            def body(q, k_pool, v_pool, pos_pool, block_table, pos):
                out = _pa.paged_decode_attention(q, k_pool, v_pool, pos_pool,
                                                 block_table, pos,
                                                 interpret=_interpret())
                return _gather_heads(out, part)

            b, m = part.batch_spec, part.model
            return jax.shard_map(
                body, mesh=part.mesh,
                in_specs=(P(b, m, None), P(None, None, m, None),
                          P(None, None, m, None), P(None, None),
                          P(b, None), P(b)),
                out_specs=P(b, None, None), check_vma=False)(
                q, k_pool, v_pool, pos_pool, block_table, pos)
    return ops.paged_decode_attention(q, k_pool, v_pool, pos_pool,
                                      block_table, pos)


def paged_decode_attention_q8(q: jax.Array, k_pool: jax.Array,
                              v_pool: jax.Array, k_scale: jax.Array,
                              v_scale: jax.Array, pos_pool: jax.Array,
                              block_table: jax.Array,
                              pos: jax.Array) -> jax.Array:
    """Quantized paged decode with the same partitioning as
    :func:`paged_decode_attention`: block-table rows [B] over the DP axes,
    pooled KV heads over 'model' when they divide.  The f32 scale pools
    [N,KV] shard their head axis alongside the int8 payload — each shard
    dequantizes its own heads' tiles in-loop."""
    rules = current_rules() or {}
    mesh = _active_mesh(rules)
    if mesh is not None:
        part = _decode_partition(rules, mesh, q.shape[0], k_pool.shape[2])
        if part is not None:
            def body(q, k_pool, v_pool, k_scale, v_scale, pos_pool,
                     block_table, pos):
                out = _pa.paged_decode_attention_q8(
                    q, k_pool, v_pool, k_scale, v_scale, pos_pool,
                    block_table, pos, interpret=_interpret())
                return _gather_heads(out, part)

            b, m = part.batch_spec, part.model
            return jax.shard_map(
                body, mesh=part.mesh,
                in_specs=(P(b, m, None), P(None, None, m, None),
                          P(None, None, m, None), P(None, m),
                          P(None, m), P(None, None),
                          P(b, None), P(b)),
                out_specs=P(b, None, None), check_vma=False)(
                q, k_pool, v_pool, k_scale, v_scale, pos_pool,
                block_table, pos)
    return ops.paged_decode_attention_q8(q, k_pool, v_pool, k_scale, v_scale,
                                         pos_pool, block_table, pos)


# ---------------------------------------------------------------------------
# Report (Runtime.describe)
# ---------------------------------------------------------------------------


def _axis_desc(kind: str, dim: int, axis: Optional[str], tp: int) -> str:
    if axis is None or tp <= 1:
        return f"{kind}=replicated"
    if not axis_shardable(dim, tp):
        return f"{kind}=replicated({dim}%{tp}!=0)"
    return f"{kind}/{tp}@{axis}"


def partition_report(cfg, plan, caps, knob: str = "auto") -> dict:
    """Per-kernel partition spec strings for ``Runtime.describe()``.

    Static view: head/column divisibility against the plan's mesh; the row
    (batch) dimension is a per-call property, so it is reported as the DP
    axes it *would* shard over."""
    mode = resolve_kernel_partition(knob)
    int8_vmap = (plan.grad_sync == "hierarchical_int8"
                 and plan.shape_kind == "train")
    if not plan.mesh_axes or mode == "off" or int8_vmap:
        if not plan.mesh_axes:
            why = "single-device"
        elif mode == "off":
            why = "off"
        else:
            # _make_compressed_step keeps the kernels replicated: shard_map
            # regions cannot ride inside the per-pod spmd vmap
            why = "hierarchical_int8: kernels ride the per-pod vmap"
        return {k: f"replicated ({why})"
                for k in ("flash_train", "fused_ffn", "flash_decode",
                          "paged_decode", "paged_decode_q8")}
    heads_axis = plan.act_rules.get("heads_act")
    mlp_axis = plan.act_rules.get("mlp_act")
    tp_h = plan.mesh_axes.get(heads_axis, 1) if heads_axis else 1
    tp_f = plan.mesh_axes.get(mlp_axis, 1) if mlp_axis else 1
    rows = "+".join(plan.batch_axes) or None
    row_desc = f"rows@{rows}" if rows else "rows=replicated"
    return {
        "flash_train": ", ".join([
            _axis_desc("heads", cfg.num_heads, heads_axis, tp_h), row_desc])
        if caps.supports_flash_train else "n/a (capability)",
        "fused_ffn": ", ".join([
            _axis_desc("columns", cfg.d_ff or 0, mlp_axis, tp_f), row_desc])
        if caps.supports_fused_ffn else "n/a (capability)",
        "flash_decode": ", ".join([
            row_desc,
            _axis_desc("kv_heads", cfg.num_kv_heads, heads_axis, tp_h)])
        if caps.supports_flash_decode else "n/a (capability)",
        "paged_decode": ", ".join([
            row_desc,
            _axis_desc("kv_heads", cfg.num_kv_heads, heads_axis, tp_h)])
        if caps.supports_paged_decode else "n/a (capability)",
        "paged_decode_q8": ", ".join([
            row_desc,
            _axis_desc("kv_heads", cfg.num_kv_heads, heads_axis, tp_h)])
        if caps.supports_quantized_kv else "n/a (capability)",
    }
