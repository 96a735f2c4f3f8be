"""Dense gated MLP (SwiGLU / GeGLU).

The SwiGLU path can route through the fused Pallas kernel
(kernels/fused_ffn.py — differentiable, hidden activations never round-trip
HBM) via the ``ffn_impl`` activation rule, resolved through
``kernels.ops.resolve_ffn_impl`` ("auto" = Pallas on TPU, ref elsewhere;
``REPRO_FFN_IMPL`` override).  ``fused_ffn_supported`` gates on the
activation (the kernel is SwiGLU-only — GeGLU archs keep the jnp path) and
block divisibility.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, PSpec
from repro.models.layers import act_fn
from repro.models.sharding import current_rules, shard


def mlp_specs(cfg: ModelConfig, d_ff: int | None = None) -> dict:
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    return {
        "wi_gate": PSpec((D, F), ("embed", "mlp"), init=f"scaled:{D}"),
        "wi_up": PSpec((D, F), ("embed", "mlp"), init=f"scaled:{D}"),
        "wo": PSpec((F, D), ("mlp", "embed"), init=f"scaled:{F}"),
    }


def fused_ffn_supported(cfg: ModelConfig, n_rows: int, d_ff: int) -> bool:
    """Whether the fused Pallas SwiGLU kernel can express this FFN call.

    The kernel hard-codes silu gating (GeGLU archs fall back to the jnp
    path) and its grid needs both the flattened row count and the hidden
    width to split into equal blocks."""
    from repro.kernels.fused_ffn import blocks_ok
    return cfg.mlp_act == "silu" and blocks_ok(n_rows, d_ff)


def mlp(x: jax.Array, params: dict, cfg: ModelConfig) -> jax.Array:
    w = params
    B, S, D = x.shape
    F = w["wi_gate"].shape[-1]
    rules = current_rules() or {}
    from repro.kernels import ops as kernel_ops
    impl = kernel_ops.resolve_ffn_impl(rules.get("ffn_impl", "auto"))
    if impl == "pallas" and fused_ffn_supported(cfg, B * S, F):
        from repro.kernels import partition as kernel_partition
        y = kernel_partition.swiglu_ffn(
            x.reshape(B * S, D), w["wi_gate"].astype(x.dtype),
            w["wi_up"].astype(x.dtype), w["wo"].astype(x.dtype))
        return shard(y.reshape(B, S, D), "batch", "seq_act", "embed_act")
    act = act_fn(cfg.mlp_act)
    gate = jnp.einsum("bsd,df->bsf", x, w["wi_gate"].astype(x.dtype))
    up = jnp.einsum("bsd,df->bsf", x, w["wi_up"].astype(x.dtype))
    h = act(gate) * up
    h = shard(h, "batch", None, "mlp_act")
    y = jnp.einsum("bsf,fd->bsd", h, w["wo"].astype(x.dtype))
    return shard(y, "batch", "seq_act", "embed_act")
