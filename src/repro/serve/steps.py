"""Serve steps: prefill (context -> caches) and decode (one token).

These are the functions the dry-run lowers for the ``prefill_*`` /
``decode_*`` / ``long_*`` shapes, and the engine (serve/engine.py) jits for
actual batched serving.  Activation-sharding rules come from the Plan the
same way the train step's do, so the serving path exercises the identical
distribution machinery.

Serving fast path (engine-only knobs; the dry-run keeps the legacy
contracts):

* ``make_prefill_step`` accepts an optional ``batch["lengths"]`` [B] int32 —
  right-padded multi-request admission batches.  Next-token logits are
  gathered at each row's true last position and pad cache entries are
  invalidated (``kvcache.mask_prefill_pos``) so decode never attends to
  them.
* ``make_decode_step(..., advance_pos=True)`` returns
  ``(token [B,1], caches, pos+1)`` so the engine can keep tokens and
  positions device-resident across ticks (no per-tick host round-trip).
* ``make_decode_step(..., attn_impl=...)`` selects the decode attention:
  ``"pallas"`` routes eligible layers through the flash-decode kernel
  (kernels/decode_attention.py), ``"ref"`` keeps the jnp softmax path,
  ``"auto"`` picks Pallas on TPU backends and the reference path elsewhere
  (interpret-mode Pallas on CPU is for numerics, not speed).  The
  ``REPRO_DECODE_ATTN`` env var overrides all of it.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.topology import Plan
from repro.models.registry import (capabilities, model_chunk_prefill,
                                   model_decode_step,
                                   model_paged_decode_step, model_prefill)
from repro.models.common import ModelConfig
from repro.models.sharding import activation_sharding
from repro.serve import kvcache


def greedy_sample(logits: jax.Array) -> jax.Array:
    """logits [B,1,V] (possibly vocab-sharded) -> next token [B] int32."""
    with jax.named_scope("head"):
        return jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1) \
            .astype(jnp.int32)


def temperature_sample(logits: jax.Array, key: jax.Array,
                       temperature: float = 1.0) -> jax.Array:
    scaled = logits[:, -1].astype(jnp.float32) / max(temperature, 1e-4)
    return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)


DECODE_ATTN_CHOICES = ("auto", "pallas", "ref", "paged", "paged_q8")


def resolve_decode_attn_impl(impl: str, cfg: ModelConfig,
                             kv_layout: str = "dense",
                             kv_dtype: str = "f32") -> str:
    """Serve decode-attention backend policy.

    "auto" -> the layout's Pallas kernel on TPU-capable backends ("pallas"
    for the dense cache, "paged" for the pooled block-table layout,
    "paged_q8" for the int8 pooled layout), "ref" elsewhere.  Explicit
    choices are honored as-is (CPU Pallas runs in interpret mode — the
    numerics-validation path); "pallas" under ``kv_layout="paged"`` means
    the layout's native kernel, i.e. "paged" (or "paged_q8" when
    ``kv_dtype="int8"``).  ``REPRO_DECODE_ATTN`` overrides everything;
    unknown values fail fast instead of silently selecting a fallback (the
    shared ``kernels.ops`` policy), and layout/dtype contradictions —
    "paged" with a dense layout, "paged_q8" without an int8 pool, "paged"
    with one — also fail fast.  Archs whose registry capabilities rule the
    kernel out (``supports_flash_decode`` is False, e.g. logit softcap —
    no Pallas decode kernel has a softcap variant) resolve to "ref" (the
    gather path carries softcap and, under int8, dequantizes); per-layer
    shape eligibility is still re-checked at trace time
    (models.attention.pallas_decode_supported /
    models.attention.paged_pallas_supported)."""
    from repro.kernels.ops import _resolve_impl
    impl = _resolve_impl(impl, "REPRO_DECODE_ATTN", DECODE_ATTN_CHOICES,
                         "decode-attention")
    caps = capabilities(cfg)
    if kv_layout == "paged":
        native = "paged_q8" if kv_dtype == "int8" else "paged"
        if impl == "pallas":
            impl = native
        if impl in ("paged", "paged_q8") and impl != native:
            raise ValueError(
                f"decode-attention impl {impl!r} contradicts "
                f"kv_dtype={kv_dtype!r} (the int8 pool's native kernel is "
                f"'paged_q8', the f32 pool's is 'paged')")
        if impl == native and not caps.supports_flash_decode:
            impl = "ref"         # ref gather carries softcap; kernel doesn't
    else:
        if impl in ("paged", "paged_q8"):
            raise ValueError(
                f"decode-attention impl {impl!r} requires kv_layout='paged' "
                f"(dense-cache engines choose between 'pallas' and 'ref')")
        if impl == "pallas" and not caps.supports_flash_decode:
            impl = "ref"
    return impl


def make_prefill_step(cfg: ModelConfig, plan: Plan, mesh, *,
                      capacity: int, attn_impl: str = "auto",
                      ffn_impl: str = "auto",
                      partition: str = "auto") -> Callable:
    """(params, batch) -> (next_token [B], caches).

    ``capacity`` is the decode-cache length the caches are padded to
    (ring-buffer size for SWA archs).  ``batch["lengths"]`` [B] int32, when
    present, marks rows as right-padded to a common bucket length: the
    next token comes from each row's true last position and pad cache
    entries are invalidated.  ``attn_impl`` / ``ffn_impl`` select the
    prefill-forward kernels (flash attention / fused SwiGLU; resolution +
    env overrides live in kernels.ops).
    """
    rules = dict(plan.act_rules)
    rules["mesh"] = mesh
    rules["train_attn_impl"] = attn_impl
    rules["ffn_impl"] = ffn_impl
    rules["kernel_partition"] = partition
    caps = capabilities(cfg)

    def prefill(params, batch):
        with activation_sharding(rules), jax.named_scope("prefill"):
            lengths = batch.get("lengths")
            if lengths is None:
                logits, caches = model_prefill(params, batch, cfg, capacity,
                                               last_only=True)
                return greedy_sample(logits), caches
            lengths = lengths.astype(jnp.int32)
            logits, caches = model_prefill(params, batch, cfg, capacity,
                                           last_index=lengths - 1)
            extra = batch.get("extra_embeds")
            if extra is not None and not caps.has_encoder:
                # frontend embeds occupy positions 0..F-1, shifting every
                # real token (mirrors model_prefill's last_index offset)
                lengths = lengths + extra.shape[1]
            caches = kvcache.mask_prefill_pos(cfg, caches, lengths)
            return greedy_sample(logits), caches

    return prefill


def make_decode_step(cfg: ModelConfig, plan: Plan, mesh, *,
                     attn_impl: str = "auto",
                     advance_pos: bool = False,
                     partition: str = "auto") -> Callable:
    """(params, token [B,1], caches, pos [B]) -> (next [B], caches).

    ``pos`` is the absolute position of the *incoming* token; ring-buffer
    write indices for SWA archs are derived inside (kvcache.write_index).
    With ``advance_pos`` the step instead returns
    ``(next [B,1], caches, pos+1)`` — the engine's device-resident hot-loop
    contract (every slot advances; inactive slots' writes are overwritten
    at re-admission).
    """
    rules = dict(plan.act_rules)
    rules["mesh"] = mesh
    rules["decode_attn_impl"] = resolve_decode_attn_impl(attn_impl, cfg)
    rules["kernel_partition"] = partition

    def decode(params, token, caches, pos):
        with activation_sharding(rules), jax.named_scope("decode"):
            logits, caches = model_decode_step(params, token, caches, cfg,
                                               pos=pos)
            nxt = greedy_sample(logits)
            if advance_pos:
                return nxt[:, None], caches, pos + 1
            return nxt, caches

    return decode


def make_paged_decode_step(cfg: ModelConfig, plan: Plan, mesh, *,
                           attn_impl: str = "auto",
                           partition: str = "auto",
                           kv_dtype: str = "f32") -> Callable:
    """(params, token [B,1], caches, pos [B], block_table [B,M],
    write_bids [B]) -> (next [B,1], caches, pos+1).

    The paged-layout analog of ``make_decode_step(advance_pos=True)``:
    ``caches`` are the pooled block caches (serve/blockpool.py),
    ``block_table`` names each slot's pool blocks and ``write_bids`` is the
    engine's per-tick write plan (the pool block this token's K/V lands in;
    TRASH for inactive slots).  Always advances positions — the engine's
    device-resident hot loop is the only consumer.  ``kv_dtype="int8"``
    expects the quantized pool layout (caches carry scale leaves) and
    resolves the impl to the in-loop-dequant kernel.
    """
    rules = dict(plan.act_rules)
    rules["mesh"] = mesh
    rules["decode_attn_impl"] = resolve_decode_attn_impl(
        attn_impl, cfg, kv_layout="paged", kv_dtype=kv_dtype)
    rules["kernel_partition"] = partition

    def decode(params, token, caches, pos, block_table, write_bids):
        with activation_sharding(rules), jax.named_scope("decode"):
            logits, caches = model_paged_decode_step(
                params, token, caches, cfg, pos=pos,
                block_table=block_table, write_bids=write_bids)
            nxt = greedy_sample(logits)
            return nxt[:, None], caches, pos + 1

    return decode


def make_mixed_step(cfg: ModelConfig, plan: Plan, mesh, *,
                    attn_impl: str = "auto",
                    partition: str = "auto") -> Callable:
    """One jitted program = decode tick over all slots + one prefill chunk.

    (params, token [N,1], caches, pos [N],
     c_tok [1,C], c_pos [1,C], c_slot [1], c_reset [1], c_last [1])
      -> (next [N,1], caches, pos+1, c_next [1])

    The scheduler's interleaving step: every decode slot advances exactly
    as in ``make_decode_step(advance_pos=True)`` while one [1,C] prompt
    chunk is appended into slot ``c_slot``'s cache row (sliced out, run
    through the chunk-append forward, spliced back in place).  Contract
    with the engine: non-decoding slots' ``pos`` are parked at
    ``attention.PAD_POS`` so their junk writes are out-of-bounds scatters
    XLA drops — the chunk slot's incrementally built row is never
    clobbered by the lock-step decode.  ``c_pos`` pads carry PAD_POS too;
    ``c_last`` gathers the chunk's final real token, whose greedy sample
    ``c_next`` seeds the slot's decode loop on the request's last chunk.
    """
    rules = dict(plan.act_rules)
    rules["mesh"] = mesh
    rules["decode_attn_impl"] = resolve_decode_attn_impl(attn_impl, cfg)
    rules["kernel_partition"] = partition

    def mixed(params, token, caches, pos, c_tok, c_pos, c_slot, c_reset,
              c_last):
        with activation_sharding(rules), jax.named_scope("mixed"):
            logits, caches = model_decode_step(params, token, caches, cfg,
                                               pos=pos)
            nxt = greedy_sample(logits)
            # cache leaves are [R, num_slots, ...]: slice the chunk slot's
            # row, append the chunk, splice back (in place under donation)
            row = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, c_slot[0], axis=1, keepdims=True), caches)
            c_logits, row = model_chunk_prefill(
                params, c_tok, row, cfg, positions=c_pos, reset=c_reset,
                last_index=c_last)
            caches = kvcache.splice_slots(caches, row, c_slot)
            return nxt[:, None], caches, pos + 1, greedy_sample(c_logits)

    return mixed


def make_paged_mixed_step(cfg: ModelConfig, plan: Plan, mesh, *,
                          attn_impl: str = "auto",
                          partition: str = "auto",
                          kv_dtype: str = "f32") -> Callable:
    """Paged-layout mixed step (decode tick + one prefill chunk).

    (params, token [N,1], caches, pos [N], block_table [N,M],
     write_bids [N], c_tok [1,C], c_pos [1,C], c_table [1,M],
     c_bids [1,C], c_last [1])
      -> (next [N,1], caches, pos+1, c_next [1])

    The chunk writes the pooled caches directly: ``c_table`` is the chunk
    owner's block chain and ``c_bids`` the per-token destination blocks
    (TRASH for pads and for prefix-shared blocks, which were written by
    their first owner).  Disjointness is what keeps decode streams
    token-identical to the unscheduled engine: decode slots write their
    own (COW-protected) blocks, the chunk writes only its exclusive
    fresh blocks, and the chunk slot's decode-tick write goes to TRASH
    (``write_plan(slot, active=False)``).
    """
    rules = dict(plan.act_rules)
    rules["mesh"] = mesh
    rules["decode_attn_impl"] = resolve_decode_attn_impl(
        attn_impl, cfg, kv_layout="paged", kv_dtype=kv_dtype)
    rules["kernel_partition"] = partition

    def mixed(params, token, caches, pos, block_table, write_bids,
              c_tok, c_pos, c_table, c_bids, c_last):
        with activation_sharding(rules), jax.named_scope("mixed"):
            logits, caches = model_paged_decode_step(
                params, token, caches, cfg, pos=pos,
                block_table=block_table, write_bids=write_bids)
            nxt = greedy_sample(logits)
            c_logits, caches = model_chunk_prefill(
                params, c_tok, caches, cfg, positions=c_pos,
                reset=jnp.zeros((1,), bool),   # paged clears via the pool
                last_index=c_last,
                paged={"block_table": c_table, "write_bids": c_bids})
            return nxt[:, None], caches, pos + 1, greedy_sample(c_logits)

    return mixed
