"""Decode-state management: KV caches (dense + SWA ring-buffer), SSM states.

Cache layout mirrors the layer-group structure: one pytree per group, every
leaf stacked along a leading "layers" axis of length group.repeats.
``run_groups_decode`` carries a group's stacked self-attention ``k``/``v``/
``pos`` through its ``lax.scan`` and writes each token's row in place at
``[layer, slot, write_idx]``; the other leaves (recurrent states, the
cross-attention memory) are scanned per layer alongside the parameters.

For sliding-window archs (mixtral) the attention cache is a ring buffer of
``window`` slots — decode at 500k context holds 4096 entries, not 500k
(this is what makes the mixtral long_500k cell feasible).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.common import LayerGroup, ModelConfig


def attn_cache_len(cfg: ModelConfig, context_len: int) -> int:
    if cfg.sliding_window is not None:
        return min(cfg.sliding_window, context_len)
    return context_len


def write_index(cfg: ModelConfig, pos: jax.Array, cache_len: int) -> jax.Array:
    """Ring-buffer write slot for the attention cache."""
    if cfg.sliding_window is not None:
        return pos % cache_len
    return pos


def _kind_cache(kind: str, cfg: ModelConfig, B: int, T: int,
                enc_len: int = 0) -> dict:
    """Concrete zero-initialized cache for one block."""
    KV, Dh, H, D = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads, cfg.d_model
    if kind.startswith("attn"):
        c = {
            "k": jnp.zeros((B, T, KV, Dh), cfg.dtype),
            "v": jnp.zeros((B, T, KV, Dh), cfg.dtype),
            "pos": jnp.full((B, T), -1, jnp.int32),
        }
        if kind == "attn_cross":
            c["xk"] = jnp.zeros((B, enc_len, KV, Dh), cfg.dtype)
            c["xv"] = jnp.zeros((B, enc_len, KV, Dh), cfg.dtype)
            c["xpos"] = jnp.full((B, enc_len), -1, jnp.int32)
        return c
    if kind.startswith("mamba"):
        Di = cfg.ssm.expand * D
        return {
            "h": jnp.zeros((B, Di, cfg.ssm.d_state), jnp.float32),
            "conv": jnp.zeros((B, cfg.ssm.d_conv - 1, Di), cfg.dtype),
        }
    if kind == "mlstm":
        Di = int(cfg.xlstm.mlstm_proj_factor * D)
        dh = Di // H
        return {
            "C": jnp.zeros((B, H, dh, dh), jnp.float32),
            "n": jnp.zeros((B, H, dh), jnp.float32),
            "m": jnp.full((B, H), -jnp.inf, jnp.float32),
            "conv": jnp.zeros((B, cfg.xlstm.conv_window - 1, Di), jnp.float32),
        }
    if kind == "slstm":
        dh = D // H
        z = jnp.zeros((B, H, dh), jnp.float32)
        return {"c": z, "n": z,
                "m": jnp.full((B, H, dh), -jnp.inf, jnp.float32), "h": z}
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, context_len: int,
               enc_len: int = 0) -> list:
    """Zero cache for decode-from-scratch (or dry-run input specs)."""
    T = attn_cache_len(cfg, context_len)
    caches = []
    for g in cfg.groups:
        per = {f"sub{j}": _kind_cache(k, cfg, batch, T, enc_len)
               for j, k in enumerate(g.pattern)}
        caches.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a, (g.repeats,) + a.shape), per))
    return caches


def abstract_cache(cfg: ModelConfig, batch: int, context_len: int,
                   enc_len: int = 0) -> list:
    """ShapeDtypeStruct version of init_cache (dry-run; no allocation)."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        jax.eval_shape(lambda: init_cache(cfg, batch, context_len, enc_len)))


def mask_prefill_pos(cfg: ModelConfig, caches: list,
                     lengths: jax.Array) -> list:
    """Invalidate right-pad entries after a padded batched prefill.

    ``lengths`` [B] int32 true prompt lengths.  Every attention-cache entry
    whose absolute position is >= its row's true length was produced by a
    pad token: its ``pos`` is set to -1 (empty) so no decode step ever
    attends to it.  K/V payloads stay in place — masking is positional
    everywhere downstream, and dense/ring write indices overwrite the slots
    as decode advances."""
    out = []
    for g, gc in zip(cfg.groups, caches):
        per = {}
        for j, kind in enumerate(g.pattern):
            c = gc[f"sub{j}"]
            if kind.startswith("attn"):
                p = c["pos"]                              # [R, B, T]
                keep = (p >= 0) & (p < lengths[None, :, None])
                c = dict(c, pos=jnp.where(keep, p, -1))
            per[f"sub{j}"] = c
        out.append(per)
    return out


def splice_slots(full, part, slots: jax.Array):
    """Write per-request prefill caches into decode slots, O(rows written).

    ``full`` leaves are [R, num_slots, ...]; ``part`` leaves [R, B, ...]
    (B = admitted batch); ``slots`` [B] int32 slot ids.  Each admitted row
    lands via ``lax.dynamic_update_index_in_dim``, which XLA performs in
    place when the caller donates ``full`` — unlike the full-cache
    ``tree.map(.at[:, slot].set)`` splice this replaces, whose cost scaled
    with num_slots x capacity.  Rows are written in reverse so duplicate
    slot ids resolve to the *earliest* row: the engine pads admission
    batches by repeating the last request, and batch-coupled compute (MoE
    capacity dropping) can make a trailing duplicate differ from its
    authentic row."""
    def one(f, p):
        p = p.astype(f.dtype)
        for i in reversed(range(p.shape[1])):
            f = jax.lax.dynamic_update_index_in_dim(f, p[:, i], slots[i],
                                                    axis=1)
        return f
    return jax.tree.map(one, full, part)


def pad_prefill_cache(cfg: ModelConfig, caches: list, prefill_len: int,
                      capacity: int, enc_len: int = 0) -> list:
    """Convert ``run_groups(collect_cache=True)`` output into decode caches.

    Prefill k/v are [R,B,S,KV,Dh] where S may already be the trimmed SWA
    window (block_forward keeps only the last ``window`` entries, so a 32k
    mixtral prefill never materializes 32k KV per layer); the entries'
    absolute positions are ``prefill_len - S .. prefill_len - 1``.  Pads /
    tail-slices the T axis to the decode capacity and, for ring-buffer
    archs, rolls entries to their ``pos % T`` slots.
    """
    out = []
    for g, gc in zip(cfg.groups, caches):
        per = {}
        for j, kind in enumerate(g.pattern):
            c = gc[f"sub{j}"]
            if kind.startswith("attn"):
                k, v = c["k"], c["v"]
                R, B, S = k.shape[0], k.shape[1], k.shape[2]
                T = attn_cache_len(cfg, capacity)
                p_start = prefill_len - S          # absolute pos of entry 0
                pos = jnp.broadcast_to(
                    jnp.arange(p_start, prefill_len, dtype=jnp.int32),
                    (R, B, S))
                if S >= T:  # keep the window tail, ring-aligned
                    start = S - T
                    k, v, pos = (k[:, :, start:], v[:, :, start:],
                                 pos[:, :, start:])
                    if cfg.sliding_window is not None:
                        # entry i holds pos p0+i and must sit at slot
                        # (p0+i) % T -> roll right by p0 % T
                        p0 = p_start + start
                        shift = p0 % T
                        k = jnp.roll(k, shift, axis=2)
                        v = jnp.roll(v, shift, axis=2)
                        pos = jnp.roll(pos, shift, axis=2)
                else:
                    padT = T - S
                    k = jnp.pad(k, ((0, 0), (0, 0), (0, padT), (0, 0), (0, 0)))
                    v = jnp.pad(v, ((0, 0), (0, 0), (0, padT), (0, 0), (0, 0)))
                    pos = jnp.pad(pos, ((0, 0), (0, 0), (0, padT)),
                                  constant_values=-1)
                nc = {"k": k, "v": v, "pos": pos}
                if kind == "attn_cross":
                    R_, B_ = c["xk"].shape[0], c["xk"].shape[1]
                    nc["xk"], nc["xv"] = c["xk"], c["xv"]
                    nc["xpos"] = jnp.broadcast_to(
                        jnp.arange(c["xk"].shape[2], dtype=jnp.int32),
                        (R_, B_, c["xk"].shape[2]))
                per[f"sub{j}"] = nc
            else:
                per[f"sub{j}"] = c
        out.append(per)
    return out
