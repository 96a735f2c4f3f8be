"""Mixture-of-Experts FFN with sort-based token dispatch.

Distribution regimes (selected by ``core.topology`` per arch × mesh):

* **EP** (``num_experts >= model-axis size``, e.g. qwen3-moe 128e on 16):
  experts sharded over 'model'; tokens are dispatched locally per device and
  exchanged with two ``lax.all_to_all`` over the model axis.  This is the
  paper-thesis placement: the high-volume token traffic rides the fast (ICI)
  tier only.

* **TP** (``num_experts <  model-axis size``, e.g. mixtral 8e, jamba 16e on
  16): every device holds all experts but only a 1/P slice of d_ff
  (column/row parallel inside each expert); token dispatch is purely local
  and the only communication is one psum of [T_local, D] partial outputs.

Both regimes (and the single-device fallback) share ``_dispatch`` /
``_combine``, so the smoke tests on one CPU device exercise the same routing
math as the 512-chip dry-run.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import ModelConfig, MoEConfig, PSpec
from repro.models.layers import act_fn
from repro.models.sharding import current_rules

# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


def moe_specs(cfg: ModelConfig, moe: MoEConfig) -> dict:
    D, E, F = cfg.d_model, moe.num_experts, moe.d_ff_expert
    return {
        "router": PSpec((D, E), ("embed", None), init=f"scaled:{D}", dtype=jnp.float32),
        "wi_gate": PSpec((E, D, F), ("experts", "embed", "expert_mlp"), init=f"scaled:{D}"),
        "wi_up": PSpec((E, D, F), ("experts", "embed", "expert_mlp"), init=f"scaled:{D}"),
        "wo": PSpec((E, F, D), ("experts", "expert_mlp", "embed"), init=f"scaled:{F}"),
    }


# ---------------------------------------------------------------------------
# Local dispatch / combine (static shapes, differentiable)
# ---------------------------------------------------------------------------


def _capacity(tokens: int, moe: MoEConfig) -> int:
    c = math.ceil(tokens * moe.top_k / moe.num_experts * moe.capacity_factor)
    return max(4, -(-c // 4) * 4)  # >=4, multiple of 4


def _route(x, router_w, moe: MoEConfig):
    """x [T,D] -> (weights [T,k] f32, experts [T,k] i32, aux_loss scalar)."""
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))  # [T,E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, moe.top_k)
    weights = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # Switch-style load-balance aux loss
    E = moe.num_experts
    dispatch_frac = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.float32), axis=1), axis=0)
    prob_frac = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(dispatch_frac * prob_frac) * moe.aux_loss_weight
    return weights, top_e, aux


def _dispatch(x, experts, capacity: int, num_experts: int):
    """Pack tokens into per-expert slots.

    x [T,D]; experts [T,k] -> xg [E*C, D], slot [T*k] (E*C = dropped),
    pair_token [T*k], keep [T*k].
    """
    T, k = experts.shape
    pair_expert = experts.reshape(-1)                       # [T*k]
    pair_token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    order = jnp.argsort(pair_expert, stable=True)
    sorted_expert = pair_expert[order]
    counts = jax.ops.segment_sum(
        jnp.ones_like(sorted_expert), sorted_expert, num_segments=num_experts)
    starts = jnp.cumsum(counts) - counts                    # exclusive
    rank = jnp.arange(T * k) - starts[sorted_expert]
    keep = rank < capacity
    slot = jnp.where(keep, sorted_expert * capacity + rank, num_experts * capacity)
    xg = jnp.zeros((num_experts * capacity + 1, x.shape[-1]), x.dtype)
    xg = xg.at[slot].set(x[pair_token[order]])
    return xg[:-1], slot, pair_token[order], keep, order


def _combine(yg, slot, pair_token_sorted, keep, weights, order, T: int):
    """Scatter expert outputs back to tokens, weighted by router probs."""
    pair_w = weights.reshape(-1)[order]                     # sorted pair weights
    yg_pad = jnp.concatenate([yg, jnp.zeros_like(yg[:1])], axis=0)
    contrib = yg_pad[slot] * (pair_w * keep).astype(yg.dtype)[:, None]
    y = jnp.zeros((T, yg.shape[-1]), yg.dtype)
    return y.at[pair_token_sorted].add(contrib)


def _expert_ffn(xg, wi_gate, wi_up, wo, act):
    """xg [E, C, D] with weights [E, D, F]/[E, F, D] -> [E, C, D]."""
    gate = jnp.einsum("ecd,edf->ecf", xg, wi_gate.astype(xg.dtype))
    up = jnp.einsum("ecd,edf->ecf", xg, wi_up.astype(xg.dtype))
    h = act(gate) * up
    return jnp.einsum("ecf,efd->ecd", h, wo.astype(xg.dtype))


# ---------------------------------------------------------------------------
# Regime bodies (run inside shard_map, or plainly when mesh is None)
# ---------------------------------------------------------------------------


def _moe_local(x2d, params, moe: MoEConfig, act):
    """Single-device MoE on local tokens. x2d [T, D]."""
    T = x2d.shape[0]
    E = moe.num_experts
    C = _capacity(T, moe)
    weights, top_e, aux = _route(x2d, params["router"], moe)
    xg, slot, ptok, keep, order = _dispatch(x2d, top_e, C, E)
    yg = _expert_ffn(xg.reshape(E, C, -1), params["wi_gate"], params["wi_up"],
                     params["wo"], act)
    y = _combine(yg.reshape(E * C, -1), slot, ptok, keep, weights, order, T)
    return y, aux


def _moe_ep_body(x2d, params, moe: MoEConfig, act, model_axis: str):
    """EP regime: experts sharded over `model_axis` (size P, E % P == 0).
    Local dispatch -> all_to_all -> expert FFN -> all_to_all back -> combine."""
    T = x2d.shape[0]
    E = moe.num_experts
    P_ = jax.lax.axis_size(model_axis)
    E_loc = E // P_
    C = _capacity(T, moe)
    weights, top_e, aux = _route(x2d, params["router"], moe)
    xg, slot, ptok, keep, order = _dispatch(x2d, top_e, C, E)
    xg = xg.reshape(E, C, -1)
    # ship token slots to their expert's device (fast-tier traffic only)
    xr = jax.lax.all_to_all(xg, model_axis, split_axis=0, concat_axis=1, tiled=True)
    # xr: [E_loc, P*C, D]; local expert weights are the device's shard
    yr = _expert_ffn(xr, params["wi_gate"], params["wi_up"], params["wo"], act)
    yg = jax.lax.all_to_all(yr, model_axis, split_axis=1, concat_axis=0, tiled=True)
    y = _combine(yg.reshape(E * C, -1), slot, ptok, keep, weights, order, T)
    return y, jax.lax.pmean(aux, model_axis)


def _moe_tp_body(x2d, params, moe: MoEConfig, act, model_axis: str):
    """TP regime: every device holds all experts with a 1/P slice of d_ff.
    Dispatch is local; the only comm is the psum of partial outputs."""
    T = x2d.shape[0]
    E = moe.num_experts
    C = _capacity(T, moe)
    weights, top_e, aux = _route(x2d, params["router"], moe)
    xg, slot, ptok, keep, order = _dispatch(x2d, top_e, C, E)
    yg = _expert_ffn(xg.reshape(E, C, -1), params["wi_gate"], params["wi_up"],
                     params["wo"], act)
    yg = jax.lax.psum(yg, model_axis)          # row-parallel partial sums
    y = _combine(yg.reshape(E * C, -1), slot, ptok, keep, weights, order, T)
    return y, aux


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------


def _chunked_tokens(fn, x2d, chunk: int):
    """Run ``fn`` ([t,D] -> (y [t,D], aux)) over token chunks via a
    rematerialized scan: the [tokens, d_ff] expert activations exist one
    chunk at a time (the vLLM-style chunked-prefill discipline applied to
    the MoE FFN — without it a 32k MoE prefill's gate/up transients alone
    exceed HBM)."""
    T, D = x2d.shape
    if T <= chunk or T % chunk != 0:
        return fn(x2d)
    nt = T // chunk

    @jax.checkpoint
    def body(carry, xc):
        y, aux = fn(xc)
        return carry + aux, y

    aux, ys = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                           x2d.reshape(nt, chunk, D))
    return ys.reshape(T, D), aux / nt


def moe_ffn(x: jax.Array, params: dict, cfg: ModelConfig, moe: MoEConfig):
    """x [B,S,D] -> (y [B,S,D], aux_loss scalar).

    Distribution is decided by the activation-sharding rules installed by the
    launcher: rules["moe_regime"] in {"ep", "tp", None} and
    rules["moe_model_axis"]/rules["moe_data_axes"] name the mesh axes.
    With no rules (single-device tests) the plain local path runs.
    ``rules["moe_chunk"]`` bounds the per-dispatch token count.
    """
    B, S, D = x.shape
    act = act_fn(cfg.mlp_act)
    rules = current_rules() or {}
    regime = rules.get("moe_regime")
    mesh = rules.get("mesh")
    moe_chunk = rules.get("moe_chunk", 0)

    if regime is None or mesh is None:
        fn = lambda xc: _moe_local(xc, params, moe, act)
        if moe_chunk:
            y, aux = _chunked_tokens(fn, x.reshape(-1, D), moe_chunk)
        else:
            y, aux = fn(x.reshape(-1, D))
        return y.reshape(B, S, D).astype(x.dtype), aux

    model_axis = rules.get("moe_model_axis", "model")
    batch_axes = rules.get("moe_batch_axes", ("pod", "data"))
    batch_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    axes_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = 1
    for a in batch_axes:
        dp *= axes_sizes[a]
    if dp > 1 and B % dp != 0:
        batch_axes = ()      # e.g. B=1 long-context decode: replicate batch

    body = _moe_ep_body if regime == "ep" else _moe_tp_body

    P_model = axes_sizes.get(model_axis, 1)
    if regime == "ep":
        w_specs = {
            "router": P(),
            "wi_gate": P(model_axis, None, None),
            "wi_up": P(model_axis, None, None),
            "wo": P(model_axis, None, None),
        }
        # CRITICAL: tokens must be *split* over the model axis inside the
        # EP region — with tokens replicated, every expert-owner dispatches
        # the same tokens and the expert FFN does P_model× redundant work
        # (observed as useful-FLOPs ratio 0.06 on jamba/qwen3-moe before
        # the fix).  Sequence splits when divisible; decode (S < P) keeps
        # the tiny replicated dispatch.
        seq_split = S % P_model == 0 and S >= P_model > 1
        x_spec = P(batch_axes if batch_axes else None,
                   model_axis if seq_split else None, None)
    else:  # tp: d_ff sliced over the model axis; tokens stay whole
        w_specs = {
            "router": P(),
            "wi_gate": P(None, None, model_axis),
            "wi_up": P(None, None, model_axis),
            "wo": P(None, model_axis, None),
        }
        x_spec = P(batch_axes if batch_axes else None, None, None)

    def mapped(xl, pl):
        fn = lambda xc: body(xc, pl, moe, act, model_axis)
        if moe_chunk:
            yl, aux = _chunked_tokens(fn, xl.reshape(-1, D), moe_chunk)
        else:
            yl, aux = fn(xl.reshape(-1, D))
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
        return yl.reshape(xl.shape), aux

    y, aux = jax.shard_map(
        mapped, mesh=mesh,
        in_specs=(x_spec, w_specs),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, {k: params[k] for k in w_specs})
    return y.astype(x.dtype), aux
