"""Block assembly and layer-group scan machinery.

A model body is a tuple of ``LayerGroup``s; each group's parameters are
stacked along a leading "layers" axis and the group lowers to a single
``lax.scan`` (keeps HLO size independent of depth — 52-layer granite compiles
as fast as a 4-layer toy).  Heterogeneous stacks (jamba's 1:7 attn:mamba
interleave with alternating MoE) unroll their *pattern* inside the scan body.

Block kinds
  attn        self-attention + dense MLP
  attn_moe    self-attention + MoE FFN
  attn_nc     non-causal self-attention + dense MLP (encoders)
  attn_cross  self-attn + cross-attn + dense MLP (enc-dec decoders)
  mamba       mamba mixer + dense MLP
  mamba_nof   mamba mixer only (no FFN)
  mamba_moe   mamba mixer + MoE FFN
  mlstm       mLSTM block (FFN built in via gated projections)
  slstm       sLSTM block (internal gated FFN)
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import ssm as ssm_mod
from repro.models.attention import (attention, attention_chunk_append,
                                    attention_chunk_append_paged,
                                    attention_decode,
                                    attention_decode_paged, attention_specs)
from repro.models.common import LayerGroup, ModelConfig, PSpec, is_pspec
from repro.models.layers import rmsnorm, rmsnorm_spec
from repro.models.mlp import mlp, mlp_specs
from repro.models.moe import moe_ffn, moe_specs
from repro.models.sharding import shard

# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------


def block_specs(kind: str, cfg: ModelConfig) -> dict:
    D = cfg.d_model
    s: dict[str, Any] = {"norm1": rmsnorm_spec(D)}
    if kind.startswith("attn"):
        s["attn"] = attention_specs(cfg)
        if kind == "attn_cross":
            s["norm_x"] = rmsnorm_spec(D)
            s["xattn"] = attention_specs(cfg, cross=True)
        s["norm2"] = rmsnorm_spec(D)
        s["ffn"] = moe_specs(cfg, cfg.moe) if kind == "attn_moe" else mlp_specs(cfg)
    elif kind.startswith("mamba"):
        s["mixer"] = ssm_mod.mamba_specs(cfg, cfg.ssm)
        if kind == "mamba_moe":
            s["norm2"] = rmsnorm_spec(D)
            s["ffn"] = moe_specs(cfg, cfg.moe)
        elif kind == "mamba":
            s["norm2"] = rmsnorm_spec(D)
            s["ffn"] = mlp_specs(cfg)
    elif kind == "mlstm":
        s["mixer"] = ssm_mod.mlstm_specs(cfg, cfg.xlstm)
    elif kind == "slstm":
        s["mixer"] = ssm_mod.slstm_specs(cfg, cfg.xlstm)
    else:
        raise ValueError(kind)
    return s


def stack_specs(specs, n: int):
    """Add a leading ("layers", n) axis to every PSpec leaf."""
    return jax.tree.map(
        lambda p: PSpec((n,) + p.shape, ("layers",) + p.axes, p.init, p.dtype),
        specs, is_leaf=is_pspec)


def group_specs(group: LayerGroup, cfg: ModelConfig) -> dict:
    per_layer = {f"sub{j}": block_specs(kind, cfg)
                 for j, kind in enumerate(group.pattern)}
    return stack_specs(per_layer, group.repeats)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def block_forward(kind: str, x, p, cfg: ModelConfig, *, positions,
                  attn_mode: str, causal: bool = True, memory=None,
                  collect_cache: bool = False):
    """One block. Returns (x, aux_loss, cache_or_None)."""
    aux = jnp.zeros((), jnp.float32)
    cache = None
    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    if kind.startswith("attn"):
        with jax.named_scope("attn"):
            if collect_cache:
                a, (k, v) = attention(h, p["attn"], cfg, positions=positions,
                                      causal=causal and kind != "attn_nc",
                                      mode=attn_mode, return_kv=True)
                if cfg.sliding_window is not None and \
                        k.shape[1] > cfg.sliding_window:
                    # SWA: only the last `window` entries can ever be
                    # attended again — trimming here keeps the per-layer
                    # prefill cache O(window), not O(S) (the 32k mixtral
                    # prefill cell)
                    k = k[:, -cfg.sliding_window:]
                    v = v[:, -cfg.sliding_window:]
                cache = {"k": k, "v": v}
            else:
                a = attention(h, p["attn"], cfg, positions=positions,
                              causal=causal and kind != "attn_nc",
                              mode=attn_mode)
            x = x + a
            if kind == "attn_cross":
                hx = rmsnorm(x, p["norm_x"], cfg.norm_eps)
                if collect_cache:
                    a2, (xk, xv) = attention(hx, p["xattn"], cfg,
                                             kv_x=memory, causal=False,
                                             mode=attn_mode, return_kv=True)
                    cache.update({"xk": xk, "xv": xv})
                    x = x + a2
                else:
                    x = x + attention(hx, p["xattn"], cfg, kv_x=memory,
                                      causal=False, mode=attn_mode)
        with jax.named_scope("ffn"):
            h2 = rmsnorm(x, p["norm2"], cfg.norm_eps)
            if kind == "attn_moe":
                f, aux = moe_ffn(h2, p["ffn"], cfg, cfg.moe)
            else:
                f = mlp(h2, p["ffn"], cfg)
            x = x + f
    elif kind.startswith("mamba"):
        if collect_cache:
            m, (hstate, buf) = ssm_mod.mamba(h, p["mixer"], cfg, cfg.ssm,
                                             return_state=True)
            cache = {"h": hstate, "conv": buf}
        else:
            m = ssm_mod.mamba(h, p["mixer"], cfg, cfg.ssm)
        x = x + m
        if kind != "mamba_nof":
            h2 = rmsnorm(x, p["norm2"], cfg.norm_eps)
            if kind == "mamba_moe":
                f, aux = moe_ffn(h2, p["ffn"], cfg, cfg.moe)
            else:
                f = mlp(h2, p["ffn"], cfg)
            x = x + f
    elif kind == "mlstm":
        m, st = ssm_mod.mlstm(h, p["mixer"], cfg, cfg.xlstm)
        if collect_cache:
            cache = {"C": st[0], "n": st[1], "m": st[2], "conv": st[3]}
        x = x + m
    elif kind == "slstm":
        m, st = ssm_mod.slstm(h, p["mixer"], cfg, cfg.xlstm)
        if collect_cache:
            cache = {"c": st[0], "n": st[1], "m": st[2], "h": st[3]}
        x = x + m
    else:
        raise ValueError(kind)
    return shard(x, "batch", "seq_act", "embed_act"), aux, cache


def _remat_wrap(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "minimal":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if policy == "full":
        return jax.checkpoint(fn)
    raise ValueError(policy)


def run_groups(x, group_params: list, cfg: ModelConfig, *, positions,
               attn_mode: str, causal: bool = True, memory=None,
               remat: Optional[str] = None, collect_cache: bool = False):
    """Run all layer groups. Returns (x, total_aux, caches).

    caches: list (per group) of stacked-cache pytrees (or None)."""
    remat = remat if remat is not None else cfg.remat_policy
    total_aux = jnp.zeros((), jnp.float32)
    caches = []
    for group, gp in zip(cfg.groups, group_params):

        def body(carry, layer_p):
            xx, aux_acc = carry
            layer_caches = {}
            for j, kind in enumerate(group.pattern):
                xx, aux, cache = block_forward(
                    kind, xx, layer_p[f"sub{j}"], cfg, positions=positions,
                    attn_mode=attn_mode, causal=causal, memory=memory,
                    collect_cache=collect_cache)
                aux_acc = aux_acc + aux
                if collect_cache:
                    layer_caches[f"sub{j}"] = cache
            return (xx, aux_acc), (layer_caches if collect_cache else None)

        body = _remat_wrap(body, remat)
        (x, total_aux), ys = jax.lax.scan(body, (x, total_aux), gp)
        caches.append(ys)
    return x, total_aux, caches


# ---------------------------------------------------------------------------
# Decode (one token; caches threaded through the scans)
# ---------------------------------------------------------------------------


def block_decode(kind: str, x, p, cfg: ModelConfig, cache: dict, *,
                 pos, write_idx, layer=None, memory=None, paged=None):
    """One block, one token. Returns (x, new_cache).

    ``paged`` = {"block_table": [B,M], "write_bids": [B]} switches the
    attention cache to the pooled paged layout (cache leaves are then the
    per-layer block pools); dense/ring layouts take the ``write_idx``
    path, where the self-attention ``k``/``v``/``pos`` leaves are the
    group's whole stacked arrays and ``layer`` indexes them (see
    :func:`run_groups_decode`)."""
    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    if kind.startswith("attn"):
        with jax.named_scope("attn"):
            if paged is not None:
                if "k_scale" in cache:  # int8 pool: scale leaves ride along
                    a, kc, vc, kp, ksc, vsc = attention_decode_paged(
                        h, p["attn"], cfg, k_pool=cache["k"],
                        v_pool=cache["v"], pos_pool=cache["pos"],
                        block_table=paged["block_table"],
                        write_bids=paged["write_bids"], pos=pos,
                        k_scale_pool=cache["k_scale"],
                        v_scale_pool=cache["v_scale"])
                    cache = dict(cache, k_scale=ksc, v_scale=vsc)
                else:
                    a, kc, vc, kp = attention_decode_paged(
                        h, p["attn"], cfg, k_pool=cache["k"],
                        v_pool=cache["v"], pos_pool=cache["pos"],
                        block_table=paged["block_table"],
                        write_bids=paged["write_bids"], pos=pos)
            else:
                a, kc, vc, kp = attention_decode(
                    h, p["attn"], cfg, k_cache=cache["k"],
                    v_cache=cache["v"], kv_positions=cache["pos"], pos=pos,
                    write_idx=write_idx, layer=layer)
            cache = dict(cache, k=kc, v=vc, pos=kp)
            x = x + a
            if kind == "attn_cross":
                hx = rmsnorm(x, p["norm_x"], cfg.norm_eps)
                a2, _, _, _ = attention_decode(
                    hx, p["xattn"], cfg, k_cache=cache["xk"],
                    v_cache=cache["xv"], kv_positions=cache["xpos"],
                    pos=pos, cross=True)
                x = x + a2
        with jax.named_scope("ffn"):
            h2 = rmsnorm(x, p["norm2"], cfg.norm_eps)
            if kind == "attn_moe":
                f, _ = moe_ffn(h2, p["ffn"], cfg, cfg.moe)
            else:
                f = mlp(h2, p["ffn"], cfg)
            x = x + f
    elif kind.startswith("mamba"):
        m, hs, buf = ssm_mod.mamba_decode(h, p["mixer"], cfg, cfg.ssm,
                                          cache["h"], cache["conv"])
        cache = dict(cache, h=hs, conv=buf)
        x = x + m
        if kind != "mamba_nof":
            h2 = rmsnorm(x, p["norm2"], cfg.norm_eps)
            if kind == "mamba_moe":
                f, _ = moe_ffn(h2, p["ffn"], cfg, cfg.moe)
            else:
                f = mlp(h2, p["ffn"], cfg)
            x = x + f
    elif kind == "mlstm":
        m, st = ssm_mod.mlstm_decode(h, p["mixer"], cfg, cfg.xlstm,
                                     (cache["C"], cache["n"], cache["m"], cache["conv"]))
        cache = dict(cache, C=st[0], n=st[1], m=st[2], conv=st[3])
        x = x + m
    elif kind == "slstm":
        m, st = ssm_mod.slstm_decode(h, p["mixer"], cfg, cfg.xlstm,
                                     (cache["c"], cache["n"], cache["m"], cache["h"]))
        cache = dict(cache, c=st[0], n=st[1], m=st[2], h=st[3])
        x = x + m
    else:
        raise ValueError(kind)
    return x, cache


def _carried_leaves(kind: str, paged) -> tuple:
    """The cache leaves of one block that ride the decode scan as its carry:
    a dense/ring self-attention cache, written in place at the layer's
    index.  Paged pools, recurrent states and cross-attention memory are
    scanned per layer instead."""
    if paged is None and kind.startswith("attn"):
        return ("k", "v", "pos")
    return ()


def run_groups_decode(x, group_params: list, caches: list, cfg: ModelConfig, *,
                      pos, write_idx, paged=None):
    """One-token step through all groups; caches updated functionally.

    A dense/ring self-attention cache (``k``, ``v``, ``pos``, each stacked
    ``[L, ...]``) is the scan's carry: each layer writes its new row at
    ``[layer, b, write_idx]`` and reads its own slice, so under donation
    the stacked arrays are updated in place and never copied.  Every other
    leaf (recurrent states, cross-attention memory, paged pools) is scanned
    per layer as ``xs`` and gathered back as ``ys``.

    ``paged`` (block table + per-tick write plan) applies to every
    attention layer — one table serves all layers, the pool-per-layer
    paged-KV contract."""
    new_caches = []
    for group, gp, gc in zip(cfg.groups, group_params, caches):
        keys = {f"sub{j}": _carried_leaves(kind, paged)
                for j, kind in enumerate(group.pattern)}
        carried = {s: {k: gc[s][k] for k in ks} for s, ks in keys.items()}
        scanned = {s: {k: a for k, a in gc[s].items() if k not in ks}
                   for s, ks in keys.items()}

        def body(carry, xs):
            xx, stacked = carry
            layer_p, layer_c, layer = xs
            stacked, layer_c = dict(stacked), dict(layer_c)
            for j, kind in enumerate(group.pattern):
                s = f"sub{j}"
                xx, c = block_decode(
                    kind, xx, layer_p[s], cfg, {**layer_c[s], **stacked[s]},
                    pos=pos, write_idx=write_idx, layer=layer, paged=paged)
                stacked[s] = {k: c[k] for k in keys[s]}
                layer_c[s] = {k: a for k, a in c.items() if k not in keys[s]}
            return (xx, stacked), layer_c

        (x, carried), nc = jax.lax.scan(
            body, (x, carried), (gp, scanned, jnp.arange(group.repeats)))
        new_caches.append({s: {**nc[s], **carried[s]} for s in keys})
    return x, new_caches


# ---------------------------------------------------------------------------
# Chunked prefill (C tokens appended to the caches; scheduler fast path)
# ---------------------------------------------------------------------------


def block_chunk(kind: str, x, p, cfg: ModelConfig, cache: dict, *,
                positions, reset, paged=None):
    """One block, one prompt chunk [B,C].  Returns (x, new_cache).

    Attention-family blocks only (the ``supports_chunked_prefill``
    capability gate): recurrent mixers would need a sequential in-chunk
    scan, which is exactly the full-prefill path this mode replaces."""
    if not kind.startswith("attn") or kind == "attn_cross":
        raise ValueError(
            f"chunked prefill only supports self-attention blocks; "
            f"got block kind {kind!r}")
    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    with jax.named_scope("attn"):
        if paged is not None:
            if "k_scale" in cache:      # int8 pool: scale leaves ride along
                a, kc, vc, kp, ksc, vsc = attention_chunk_append_paged(
                    h, p["attn"], cfg, k_pool=cache["k"], v_pool=cache["v"],
                    pos_pool=cache["pos"], block_table=paged["block_table"],
                    write_bids=paged["write_bids"], positions=positions,
                    k_scale_pool=cache["k_scale"],
                    v_scale_pool=cache["v_scale"])
                cache = dict(cache, k_scale=ksc, v_scale=vsc)
            else:
                a, kc, vc, kp = attention_chunk_append_paged(
                    h, p["attn"], cfg, k_pool=cache["k"], v_pool=cache["v"],
                    pos_pool=cache["pos"], block_table=paged["block_table"],
                    write_bids=paged["write_bids"], positions=positions)
        else:
            a, kc, vc, kp = attention_chunk_append(
                h, p["attn"], cfg, k_cache=cache["k"], v_cache=cache["v"],
                kv_positions=cache["pos"], positions=positions, reset=reset)
        cache = dict(cache, k=kc, v=vc, pos=kp)
        x = x + a
    with jax.named_scope("ffn"):
        h2 = rmsnorm(x, p["norm2"], cfg.norm_eps)
        if kind == "attn_moe":
            f, _ = moe_ffn(h2, p["ffn"], cfg, cfg.moe)
        else:
            f = mlp(h2, p["ffn"], cfg)
        x = x + f
    return x, cache


def run_groups_chunk(x, group_params: list, caches: list, cfg: ModelConfig, *,
                     positions, reset, paged=None):
    """One prompt-chunk step through all groups; caches updated
    functionally — the chunk analog of :func:`run_groups_decode` (same
    scan threading, C queries instead of one)."""
    new_caches = []
    for group, gp, gc in zip(cfg.groups, group_params, caches):

        def body(xx, scanned):
            layer_p, layer_c = scanned
            for j, kind in enumerate(group.pattern):
                xx, layer_c[f"sub{j}"] = block_chunk(
                    kind, xx, layer_p[f"sub{j}"], cfg, layer_c[f"sub{j}"],
                    positions=positions, reset=reset, paged=paged)
            return xx, layer_c

        x, nc = jax.lax.scan(body, x, (gp, gc))
        new_caches.append(nc)
    return x, new_caches
