"""Decoder-only causal language model (all non-enc-dec archs).

Public surface:
  lm_specs(cfg)                           param PSpec tree
  lm_forward(params, tokens, cfg, ...)    vocab-sharded logits (+aux, caches)
  lm_loss(params, batch, cfg, ...)        scalar loss (sharded CE + MoE aux)
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models.blocks import (group_specs, run_groups, run_groups_chunk,
                                 run_groups_decode)
from repro.models.common import ModelConfig, PSpec
from repro.models.layers import (chunked_softmax_xent, cross_entropy,
                                 embedding_spec, lm_head, rmsnorm,
                                 rmsnorm_spec)
from repro.models.sharding import current_rules, shard


def lm_specs(cfg: ModelConfig) -> dict:
    s: dict[str, Any] = {
        "embed": embedding_spec(cfg),
        "final_norm": rmsnorm_spec(cfg.d_model),
        "groups": [group_specs(g, cfg) for g in cfg.groups],
    }
    if not cfg.tie_embeddings:
        s["unembed"] = PSpec((cfg.padded_vocab, cfg.d_model),
                             ("vocab", "embed"), init=f"scaled:{cfg.d_model}")
    if cfg.pos_emb == "learned":
        assert cfg.max_position_embeddings > 0
        s["pos_embed"] = PSpec((cfg.max_position_embeddings, cfg.d_model),
                               (None, "embed"), init="normal")
    return s


def _embed(params, tokens, cfg: ModelConfig, positions=None):
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(jnp.sqrt(cfg.d_model), cfg.dtype)
    if cfg.pos_emb == "learned":
        if positions is None:
            positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
        x = x + jnp.take(params["pos_embed"], positions, axis=0).astype(cfg.dtype)
    return shard(x, "batch", "seq_act", "embed_act")


def _unembed_table(params, cfg: ModelConfig):
    return params["embed"] if cfg.tie_embeddings else params["unembed"]


def lm_forward(params, tokens, cfg: ModelConfig, *,
               positions=None, attn_mode: str = "heads",
               extra_embeds=None, collect_cache: bool = False,
               last_only: bool = False, last_index=None):
    """tokens [B,S] -> logits [B,S_total,V] (vocab-sharded).

    ``extra_embeds`` [B,F,D] (vision/audio stub embeddings) are prepended;
    positions then cover the concatenated sequence.  ``last_only`` projects
    logits for the final position only (serving prefill: [B,1,V]);
    ``last_index`` [B] int32 picks a per-row position instead (right-padded
    batched prefill — rows of different true lengths in one call)."""
    x = _embed(params, tokens, cfg, positions)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(cfg.dtype), x], axis=1)
        # positions cover the concatenated sequence: the standard arange
        positions = None
    B, S, _ = x.shape
    # positions=None propagates "standard arange" down to attention, which
    # generates it — and may route through the Pallas flash kernel (whose
    # causal mask bakes arange positions in)
    x, aux, caches = run_groups(
        x, params["groups"], cfg, positions=positions, attn_mode=attn_mode,
        collect_cache=collect_cache)
    if last_index is not None:
        x = jnp.take_along_axis(
            x, last_index.astype(jnp.int32)[:, None, None], axis=1)
    elif last_only:
        x = x[:, -1:]
    with jax.named_scope("head"):
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = lm_head(x, _unembed_table(params, cfg), cfg)
        logits = shard(logits, "batch", None, "vocab_act")
    return logits, aux, caches


def lm_loss(params, batch: dict, cfg: ModelConfig, *,
            attn_mode: str = "heads") -> tuple[jax.Array, dict]:
    """batch: tokens [B,S], labels [B,S] (-1 = ignore), optional
    extra_embeds.  Returns (loss, metrics).

    With the ``ce_chunk`` activation rule set, the lm_head + CE run fused
    over sequence chunks (the [B,S,V] logits never materialize) — required
    for the large-vocab archs at train_4k scale."""
    rules = current_rules() or {}
    ce_chunk = rules.get("ce_chunk", 0)
    labels = batch["labels"]

    if ce_chunk:
        x = _embed(params, batch["tokens"], cfg)
        extra = batch.get("extra_embeds")
        if extra is not None:
            x = jnp.concatenate([extra.astype(cfg.dtype), x], axis=1)
        S = x.shape[1]
        # positions=None = standard arange (keeps the flash fast path
        # eligible on the large-vocab ce_chunk train cells)
        x, aux, _ = run_groups(x, params["groups"], cfg, positions=None,
                               attn_mode=attn_mode)
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        if S != labels.shape[1]:
            pad = S - labels.shape[1]
            labels = jnp.pad(labels, ((0, 0), (pad, 0)), constant_values=-1)
        ce = chunked_softmax_xent(x, _unembed_table(params, cfg), labels,
                                  cfg, ce_chunk)
    else:
        logits, aux, _ = lm_forward(
            params, batch["tokens"], cfg, attn_mode=attn_mode,
            extra_embeds=batch.get("extra_embeds"))
        if logits.shape[1] != labels.shape[1]:   # frontend pos: no loss
            pad = logits.shape[1] - labels.shape[1]
            labels = jnp.pad(labels, ((0, 0), (pad, 0)), constant_values=-1)
        ce = cross_entropy(logits, labels)
    loss = ce + aux
    return loss, {"loss": loss, "ce": ce, "moe_aux": aux}


def lm_chunk_prefill(params, tokens, caches, cfg: ModelConfig, *,
                     positions, reset, last_index, paged=None):
    """tokens [B,C] (one prompt chunk, pad positions = PAD_POS) ->
    (logits [B,1,V], new caches).

    Chunked prefill: appends C tokens of KV into the decode caches at
    absolute ``positions`` [B,C] and attends with per-query positional
    masking — interleaved with decode ticks by the serve scheduler.
    ``reset`` [B] bool clears a slot's cache row before the first chunk
    (dense layout; paged slots are cleared via the block pool).
    ``last_index`` [B] gathers each row's final real-token logits."""
    emb_pos = None
    if cfg.pos_emb == "learned":
        # clip the PAD_POS sentinel so the gather stays in-table; pad
        # outputs are never read (last_index points at real tokens)
        emb_pos = jnp.minimum(positions, cfg.max_position_embeddings - 1)
    x = _embed(params, tokens, cfg, positions=emb_pos)
    x, caches = run_groups_chunk(x, params["groups"], caches, cfg,
                                 positions=positions, reset=reset,
                                 paged=paged)
    x = jnp.take_along_axis(
        x, last_index.astype(jnp.int32)[:, None, None], axis=1)
    with jax.named_scope("head"):
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = lm_head(x, _unembed_table(params, cfg), cfg)
    return logits, caches


def lm_decode_step(params, token, caches, cfg: ModelConfig, *,
                   pos, write_idx, paged=None):
    """token [B,1] -> (logits [B,1,V], new caches).

    ``paged`` = {"block_table", "write_bids"} switches the attention caches
    to the pooled paged-KV layout (see serve/blockpool.py)."""
    x = _embed(params, token, cfg,
               positions=pos[:, None] if cfg.pos_emb == "learned" else None)
    x, caches = run_groups_decode(x, params["groups"], caches, cfg,
                                  pos=pos, write_idx=write_idx, paged=paged)
    with jax.named_scope("head"):
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = lm_head(x, _unembed_table(params, cfg), cfg)
    return logits, caches
