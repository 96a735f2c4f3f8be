"""Grouped-query attention with RoPE, qk-norm, sliding windows and a
kv-cached decode path.

Sharding modes (picked by ``core.topology`` per arch × mesh):

* ``heads``    — Q/K/V repeated to full head count and sharded over the
  'model' axis (classic Megatron).  The repeat is a broadcast XLA folds into
  the dot; it is what makes GQA (kv=4/8) shardable on a 16-way axis.
* ``sequence`` — for archs whose q-head count does not divide the model axis
  (gemma-2b/granite-20b MQA 8H, llama3.2 24H, whisper 6H): Q/out are sharded
  over the *sequence* on the model axis, K/V replicated (they are tiny for
  MQA); XLA inserts the seq<->hidden reshards at block boundaries
  (Megatron-SP style).

KV-chunked online softmax (``attn_chunk_kv`` rule) bounds the score
materialization to [B,H,S,chunk] — the jnp analog of flash attention's
blocking, used for the 32k prefill cells; the Pallas kernel
(kernels/flash_attention.py) is the TPU-native version of the same blocking
and is wired into this module's train/prefill forward: the
``train_attn_impl`` activation rule (resolved through
``kernels.ops.resolve_train_attn_impl`` — "auto" = Pallas on TPU, ref
elsewhere; ``REPRO_ATTN_IMPL`` override) routes eligible layers through the
differentiable flash kernel, with ``flash_train_supported`` gating on
softcap/head-dim/block-divisibility and standard (arange) positions.
Every Pallas call here dispatches through ``kernels.partition``, which
shard_maps the kernel over the mesh (heads/'model' for the train kernel,
cache rows/DP + KV heads/'model' for the decode kernels) when the
activation rules and divisibility allow.

Decode is context-parallel: the KV cache is sharded along T (flash-decode
style); softmax over the sharded axis lowers to small all-reduces.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, PSpec
from repro.models.layers import apply_rope, rmsnorm
from repro.models.sharding import current_rules, shard

NEG_INF = -1e30  # large-negative in f32; avoids nan from (-inf) - (-inf)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


def attention_specs(cfg: ModelConfig, cross: bool = False) -> dict:
    D, H, KV, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "wq": PSpec((D, H, Dh), ("embed", "heads", "head_dim"), init=f"scaled:{D}"),
        "wk": PSpec((D, KV, Dh), ("embed", "kv_heads", "head_dim"), init=f"scaled:{D}"),
        "wv": PSpec((D, KV, Dh), ("embed", "kv_heads", "head_dim"), init=f"scaled:{D}"),
        "wo": PSpec((H, Dh, D), ("heads", "head_dim", "embed"), init=f"scaled:{H * Dh}"),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = PSpec((Dh,), ("head_dim",), init="ones")
        p["k_norm"] = PSpec((Dh,), ("head_dim",), init="ones")
    return p


# ---------------------------------------------------------------------------
# Score-level helpers
# ---------------------------------------------------------------------------


def _mask(q_pos, kv_pos, causal: bool, window: Optional[int]):
    """[B,S,T] boolean; True = attend."""
    if not causal:
        return None
    m = kv_pos[:, None, :] <= q_pos[:, :, None]
    if window is not None:
        m &= kv_pos[:, None, :] > (q_pos[:, :, None] - window)
    return m


def _full_attend(q, k, v, mask, softcap, scale):
    """q [B,S,H,dh], k/v [B,T,H,dh], mask [B,S,T] or None."""
    s = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    if mask is not None:
        s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", p, v)


def _chunked_attend(q, k, v, q_pos, kv_pos, causal, window, softcap, scale,
                    chunk: int):
    """Online-softmax over KV chunks; scores never exceed [B,H,S,chunk]."""
    B, S, H, Dh = q.shape
    T = k.shape[1]
    q_pos = jnp.broadcast_to(q_pos, (B, S))
    kv_pos = jnp.broadcast_to(kv_pos, (B, T))
    pad = (-T) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=2**30)
    nk = (T + pad) // chunk
    ks = k.reshape(B, nk, chunk, H, Dh).swapaxes(0, 1)
    vs = v.reshape(B, nk, chunk, H, Dh).swapaxes(0, 1)
    ps = kv_pos.reshape(B, nk, chunk).swapaxes(0, 1)

    # kv-position mask constants hoisted out of the scan body: the [B,S,1]
    # q-position bounds are chunk-invariant, so each iteration only does the
    # [B,S,chunk] compares against them
    q_hi = q_pos[:, :, None]                              # [B,S,1]
    q_lo = q_hi - window if (causal and window is not None) else None

    def body(carry, inp):
        m, l, acc = carry
        kc, vc, pc = inp
        s = jnp.einsum("bshd,bchd->bhsc", q, kc).astype(jnp.float32) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        valid = pc[:, None, :] <= q_hi if causal else pc[:, None, :] < 2**30
        if q_lo is not None:
            valid &= pc[:, None, :] > q_lo
        s = jnp.where(valid[:, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhsc,bchd->bshd", p.astype(q.dtype), vc).astype(jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, H, S), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    a0 = jnp.zeros((B, S, H, Dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (ks, vs, ps))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Full-sequence forward (training / prefill)
# ---------------------------------------------------------------------------


def flash_train_supported(cfg: ModelConfig, S: int, T: int, Dh: int) -> bool:
    """Whether the Pallas flash-attention kernel can express this
    train/prefill attention shape.

    The kernel has no logit-softcap variant, its VMEM claim is sized for
    head dims <= 256, and its grid needs both sequence axes to split into
    equal blocks (len <= block or len % block == 0).  Positional
    eligibility (standard arange positions for causal masking) is checked
    by the caller, which knows whether positions were auto-generated."""
    from repro.kernels.flash_attention import DEFAULT_BK, DEFAULT_BQ
    return (cfg.attn_logit_softcap is None
            and Dh <= 256
            and (S <= DEFAULT_BQ or S % DEFAULT_BQ == 0)
            and (T <= DEFAULT_BK or T % DEFAULT_BK == 0))


def _flash_attend(q, k, v, causal: bool, window: Optional[int]):
    """Route [B,S,H,dh]-layout q/k/v through the differentiable Pallas flash
    kernel ([B,H,S,dh] layout) and back.  Dispatch goes through
    ``kernels.partition``: head-sharded shard_map when the mesh and head
    count allow, today's replicated call otherwise."""
    from repro.kernels import partition as kernel_partition
    out = kernel_partition.flash_attention(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
        causal=causal, window=(window or 0) if causal else 0)
    return out.swapaxes(1, 2)


def attention(x: jax.Array, params: dict, cfg: ModelConfig, *,
              positions: Optional[jax.Array] = None,
              causal: bool = True,
              kv_x: Optional[jax.Array] = None,
              mode: str = "heads",
              return_kv: bool = False):
    """x [B,S,D] -> [B,S,D].  ``kv_x`` switches to cross-attention (no rope,
    no causal mask).  ``return_kv`` also returns grouped (k, v) for prefill
    caching.  ``positions=None`` means the standard arange — the only
    positional layout the Pallas flash kernel can express for causal
    masking, so it doubles as the flash-eligibility signal."""
    B, S, D = x.shape
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    src = x if kv_x is None else kv_x
    T = src.shape[1]
    std_positions = positions is None

    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("btd,dhk->bthk", src, params["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dhk->bthk", src, params["wv"].astype(x.dtype))

    if cfg.qk_norm and "q_norm" in params:
        q = rmsnorm(q, params["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, params["k_norm"], cfg.norm_eps)

    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if kv_x is None and cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    kv_grouped = (k, v)
    # GQA repeat -> full head count (XLA folds the broadcast into the dot)
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)

    if mode == "sequence":
        q = shard(q, "batch", "seq_model", None, None)
        k = shard(k, "batch", None, None, None)
        v = shard(v, "batch", None, None, None)
    else:
        q = shard(q, "batch", None, "heads_act", None)
        k = shard(k, "batch", None, "heads_act", None)
        v = shard(v, "batch", None, "heads_act", None)

    kv_pos = positions if kv_x is None else jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    is_causal = causal and kv_x is None
    scale = Dh ** -0.5
    rules = current_rules() or {}
    from repro.kernels import ops as kernel_ops
    impl = kernel_ops.resolve_train_attn_impl(
        rules.get("train_attn_impl", "auto"))
    use_flash = (impl == "pallas"
                 and flash_train_supported(cfg, S, T, Dh)
                 and (std_positions or not is_causal))
    chunk = rules.get("attn_chunk_kv", 0)
    if use_flash:
        out = _flash_attend(q, k, v, is_causal, cfg.sliding_window)
    elif chunk and T > chunk:
        out = _chunked_attend(q, k, v, positions, kv_pos, is_causal,
                              cfg.sliding_window, cfg.attn_logit_softcap,
                              scale, chunk)
    else:
        mask = _mask(positions, kv_pos, is_causal, cfg.sliding_window)
        out = _full_attend(q, k, v, mask, cfg.attn_logit_softcap, scale)

    out = shard(out, "batch", "seq_model" if mode == "sequence" else None,
                "heads_act" if mode != "sequence" else None, None)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    y = shard(y, "batch", "seq_act", "embed_act")
    if return_kv:
        return y, kv_grouped
    return y


# ---------------------------------------------------------------------------
# Decode step (one new token against a KV cache; context-parallel)
# ---------------------------------------------------------------------------


def pallas_decode_supported(cfg: ModelConfig, cache_len: int,
                            cross: bool = False) -> bool:
    """Whether the Pallas flash-decode kernel can serve this decode shape.

    The kernel has no logit-softcap or cross-attention variant, and its kv
    grid needs the cache length to split into equal blocks (T <= bk or
    T % bk == 0)."""
    from repro.kernels.decode_attention import DEFAULT_BK
    return (not cross
            and cfg.attn_logit_softcap is None
            and (cache_len <= DEFAULT_BK or cache_len % DEFAULT_BK == 0))


def paged_pallas_supported(cfg: ModelConfig) -> bool:
    """Whether the Pallas paged-decode kernel can serve this arch: like the
    dense flash-decode kernel it has no logit-softcap variant; block
    divisibility is structural (the pool's block axis is the grid)."""
    return cfg.attn_logit_softcap is None


def _jnp_decode_attend(q, k_cache, v_cache, kv_positions, pos,
                       cfg: ModelConfig, cross: bool = False):
    """The reference decode-attention math shared by the dense and paged
    layouts: q [B,S,H,Dh] against grouped caches [B,T,KV,Dh] with
    positional masking (kv_positions [B,T]; -1 = empty) -> out [B,S,H,Dh].

    ``pos`` is [B] (the classic one-token decode step, S == 1) or [B,S]
    per-query absolute positions (the chunked-prefill append path — each
    query attends to every cache entry at or before its own position, so
    causality *within* the chunk falls out of the same positional mask,
    provided the chunk's K/V entries are written before attending).
    """
    B, S = q.shape[0], q.shape[1]
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // KV
    q = q.reshape(B, S, KV, G, Dh)
    if cross:
        mask = (kv_positions >= 0)[:, None, None, None, :]      # [B,1,1,1,T]
    else:
        q_pos = pos[:, None] if pos.ndim == 1 else pos          # [B,S]
        valid = (kv_positions >= 0)[:, None, :]                 # [B,1,T]
        within = kv_positions[:, None, :] <= q_pos[:, :, None]  # [B,S,T]
        mask = valid & within
        if cfg.sliding_window is not None:
            mask &= kv_positions[:, None, :] > \
                (q_pos[:, :, None] - cfg.sliding_window)
        mask = mask[:, None, None, :, :]                        # [B,1,1,S,T]

    scale = Dh ** -0.5
    s = jnp.einsum("bskgd,btkd->bkgst", q, k_cache).astype(jnp.float32) * scale
    if cfg.attn_logit_softcap is not None:
        s = jnp.tanh(s / cfg.attn_logit_softcap) * cfg.attn_logit_softcap
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", p, v_cache)
    return out.reshape(B, S, H, Dh)


def attention_decode(x: jax.Array, params: dict, cfg: ModelConfig, *,
                     k_cache: jax.Array, v_cache: jax.Array,
                     kv_positions: jax.Array, pos: jax.Array,
                     write_idx: Optional[jax.Array] = None,
                     layer: Optional[jax.Array] = None,
                     cross: bool = False):
    """One-token decode against a KV cache.

    x [B,1,D]; pos [B] absolute position of the new token.

    Self-attention: the caches are the layer group's stacked
    [L,B,T,KV,Dh] (grouped heads; T may be sharded — context-parallel
    decode) and kv_positions [L,B,T] (int32; ring-buffer aware — empty
    slots carry -1); ``layer`` is this layer's index into them and
    write_idx [B] the cache slot to write (pos % window for SWA ring
    buffers).  The new K/V entry is written at [layer, b, write_idx]
    *before* attending so the token sees itself; the write touches one row
    per slot, in place when the stacked arrays are the decode scan's carry.

    Cross-attention (``cross=True``): the caches are this layer's own
    [B,T,KV,Dh] encoder memory, which is static: no write, no ``layer``.

    Returns (y [B,1,D], k_cache', v_cache', kv_positions'), shaped as given.
    """
    B, _, D = x.shape
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // KV

    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    if cfg.qk_norm and "q_norm" in params:
        q = rmsnorm(q, params["q_norm"], cfg.norm_eps)
    k_l, v_l, p_l = k_cache, v_cache, kv_positions
    if not cross:
        if cfg.use_rope:
            q = apply_rope(q, pos[:, None], cfg.rope_theta)

        k_new = jnp.einsum("btd,dhk->bthk", x, params["wk"].astype(x.dtype))
        v_new = jnp.einsum("btd,dhk->bthk", x, params["wv"].astype(x.dtype))
        if cfg.qk_norm and "k_norm" in params:
            k_new = rmsnorm(k_new, params["k_norm"], cfg.norm_eps)
        if cfg.use_rope:
            k_new = apply_rope(k_new, pos[:, None], cfg.rope_theta)

        if write_idx is None:
            write_idx = pos
        b = jnp.arange(B)
        with jax.named_scope("kv_update"):
            k_cache = k_cache.at[layer, b, write_idx].set(k_new[:, 0])
            v_cache = v_cache.at[layer, b, write_idx].set(v_new[:, 0])
            kv_positions = kv_positions.at[layer, b, write_idx].set(pos)
        k_l, v_l, p_l = k_cache[layer], v_cache[layer], kv_positions[layer]

    rules = current_rules() or {}
    if (rules.get("decode_attn_impl") == "pallas"
            and pallas_decode_supported(cfg, k_l.shape[1], cross=cross)):
        # Flash-decode Pallas kernel: online softmax over kv blocks, never
        # materializes the [T] score vector in HBM.  Positional masking
        # (incl. the SWA ring buffer) matches the jnp path below.  The
        # partition layer shards cache rows over the DP axes and KV heads
        # over 'model' when they divide (replicated dispatch otherwise).
        from repro.kernels import partition as kernel_partition
        out = kernel_partition.decode_attention(
            q[:, 0], k_l, v_l, p_l, pos, window=cfg.sliding_window or 0)
        y = jnp.einsum("bshk,hkd->bsd", out[:, None],
                       params["wo"].astype(x.dtype))
        return y, k_cache, v_cache, kv_positions

    out = _jnp_decode_attend(q, k_l, v_l, p_l, pos, cfg, cross=cross)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return y, k_cache, v_cache, kv_positions


def _quantized_block_write(pool, scale_pool, new, write_bids, off):
    """Scatter ``new`` full-precision K/V entries into an int8 pool with
    per-(block, kv-head) scales (kernels/quant.py max-abs convention).

    ``new`` is S + (KV, Dh) with index arrays ``write_bids``/``off`` of
    shape S ([B] for one-token decode, [B, C] for a prompt chunk).  An
    offset-0 write lands in a *fresh* (recycled) block, so its stale scale
    row is reset first — other writes redirect that reset at the TRASH
    block (id 1), whose contents are unobservable.  A new entry whose
    magnitude exceeds its block's scale *grows* the scale and requantizes
    the block's existing int8 payload in place (ratio == 1 exactly for
    untouched blocks, so their bits never move); entries within range
    reuse the block scale untouched.  Full precision never lands in the
    pool."""
    new = new.astype(jnp.float32)
    clear = jnp.where(off == 0, write_bids, jnp.ones_like(write_bids))
    scale_pool = scale_pool.at[clear].set(0.0)
    need = jnp.max(jnp.abs(new), axis=-1) / 127.0        # S + (KV,)
    grown = scale_pool.at[write_bids].max(need)          # [N, KV]
    ratio = scale_pool / jnp.where(grown > 0, grown, 1.0)
    pool = jnp.round(pool.astype(jnp.float32)
                     * ratio[:, None, :, None]).astype(jnp.int8)
    dest = grown[write_bids]                             # S + (KV,)
    q = jnp.clip(jnp.round(new / jnp.where(dest > 0, dest, 1.0)[..., None]),
                 -127, 127).astype(jnp.int8)
    return pool.at[write_bids, off].set(q), grown


def _dequantize_gather(pool, scale_pool, flat, dtype, shape):
    """Materialize ``pool[flat]`` int8 blocks at full precision for the
    reference gather path: per-(block, kv-head) scale broadcast over the
    [bs, Dh] tile, cast back to the activation dtype so the attention math
    keeps the same dtypes as the f32-pool path."""
    deq = pool[flat].astype(jnp.float32) * scale_pool[flat][:, None, :, None]
    return deq.astype(dtype).reshape(shape)


def attention_decode_paged(x: jax.Array, params: dict, cfg: ModelConfig, *,
                           k_pool: jax.Array, v_pool: jax.Array,
                           pos_pool: jax.Array, block_table: jax.Array,
                           write_bids: jax.Array, pos: jax.Array,
                           k_scale_pool: Optional[jax.Array] = None,
                           v_scale_pool: Optional[jax.Array] = None):
    """One-token decode against a *paged* KV pool.

    x [B,1,D]; pools [N,bs,KV,Dh] / pos_pool [N,bs] shared by every row;
    block_table [B,M] int32 names each row's blocks in order (NULL block 0
    = unused entry, permanently masked); write_bids [B] the pool block this
    token's K/V lands in (the engine's per-tick write plan — TRASH for
    inactive rows); pos [B] the token's absolute position (write offset =
    ``pos % bs``).  The new entry is inserted before attending so the token
    sees itself.

    Routing mirrors the dense path: the ``decode_attn_impl`` rule value
    "paged" selects the Pallas paged kernel (block-table gather fused into
    the grid); anything else takes the reference gather — materialize the
    row's blocks contiguously and run the same jnp masked softmax as the
    dense layout, which is what makes dense and paged engines
    token-for-token comparable.

    Quantized pools: passing ``k_scale_pool``/``v_scale_pool`` f32 [N,KV]
    marks the pools as int8 — the new token's K/V entry is quantized
    against its block's per-(block, kv-head) scale (growing it and
    requantizing the block when needed; :func:`_quantized_block_write`),
    so full precision never lands in the pool, and the rule value
    "paged_q8" selects the in-loop-dequant Pallas kernel (the reference
    gather dequantizes instead).

    Returns (y [B,1,D], k_pool', v_pool', pos_pool') — with the updated
    scale pools appended when quantized.
    """
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    B = x.shape[0]
    bs = k_pool.shape[1]
    M = block_table.shape[1]
    quantized = k_scale_pool is not None

    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    if cfg.qk_norm and "q_norm" in params:
        q = rmsnorm(q, params["q_norm"], cfg.norm_eps)
    if cfg.use_rope:
        q = apply_rope(q, pos[:, None], cfg.rope_theta)

    k_new = jnp.einsum("btd,dhk->bthk", x, params["wk"].astype(x.dtype))
    v_new = jnp.einsum("btd,dhk->bthk", x, params["wv"].astype(x.dtype))
    if cfg.qk_norm and "k_norm" in params:
        k_new = rmsnorm(k_new, params["k_norm"], cfg.norm_eps)
    if cfg.use_rope:
        k_new = apply_rope(k_new, pos[:, None], cfg.rope_theta)

    off = (pos % bs).astype(jnp.int32)
    # An offset-0 write always lands in a *fresh* block (chains only grow
    # at block boundaries, and copy-on-write duplicates full blocks), and a
    # fresh block is recycled storage whose stale ``pos`` entries would
    # otherwise pass the positional mask as phantoms — clear the block's
    # position row before writing into it.  (Quantized pools reset the
    # block's stale *scale* the same way, inside _quantized_block_write.)
    with jax.named_scope("kv_update"):
        prow = pos_pool[write_bids]                         # [B, bs]
        pos_pool = pos_pool.at[write_bids].set(
            jnp.where((off == 0)[:, None], -1, prow))
        if quantized:
            k_pool, k_scale_pool = _quantized_block_write(
                k_pool, k_scale_pool, k_new[:, 0], write_bids, off)
            v_pool, v_scale_pool = _quantized_block_write(
                v_pool, v_scale_pool, v_new[:, 0], write_bids, off)
        else:
            k_pool = k_pool.at[write_bids, off].set(k_new[:, 0])
            v_pool = v_pool.at[write_bids, off].set(v_new[:, 0])
        pos_pool = pos_pool.at[write_bids, off].set(pos)

    rules = current_rules() or {}
    impl = rules.get("decode_attn_impl")
    if (quantized and impl == "paged_q8" and paged_pallas_supported(cfg)):
        from repro.kernels import partition as kernel_partition
        out = kernel_partition.paged_decode_attention_q8(
            q[:, 0], k_pool, v_pool, k_scale_pool, v_scale_pool, pos_pool,
            block_table, pos)[:, None]
    elif (not quantized and impl == "paged"
            and paged_pallas_supported(cfg)):
        from repro.kernels import partition as kernel_partition
        out = kernel_partition.paged_decode_attention(
            q[:, 0], k_pool, v_pool, pos_pool, block_table, pos)[:, None]
    else:
        flat = block_table.reshape(-1)
        if quantized:
            k = _dequantize_gather(k_pool, k_scale_pool, flat, x.dtype,
                                   (B, M * bs, KV, Dh))
            v = _dequantize_gather(v_pool, v_scale_pool, flat, x.dtype,
                                   (B, M * bs, KV, Dh))
        else:
            k = k_pool[flat].reshape(B, M * bs, KV, Dh)
            v = v_pool[flat].reshape(B, M * bs, KV, Dh)
        kvp = pos_pool[flat].reshape(B, M * bs)
        out = _jnp_decode_attend(q, k, v, kvp, pos, cfg)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    if quantized:
        return y, k_pool, v_pool, pos_pool, k_scale_pool, v_scale_pool
    return y, k_pool, v_pool, pos_pool


# ---------------------------------------------------------------------------
# Chunked-prefill append (C tokens against a KV cache; scheduler fast path)
# ---------------------------------------------------------------------------


PAD_POS = 2 ** 30
"""Pad-token position sentinel for chunked prefill.

A chunk is a fixed [B, C] window; when fewer than C prompt tokens remain,
the tail is padded and the pad tokens carry this position.  Everything
downstream then neutralizes them for free: the dense cache write at index
``PAD_POS`` is an out-of-bounds scatter XLA drops, the paged write lands in
the TRASH block (the caller's write_bids), rope/softmax of a huge position
stay finite, and the pad rows' outputs are never read (``last_index``)."""


def _project_chunk_kv(x, params, cfg: ModelConfig, positions):
    """Shared q/k/v projection + qk-norm + rope for a chunk append.
    x [B,C,D], positions [B,C] absolute (PAD_POS on pads)."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"].astype(x.dtype))
    if cfg.qk_norm and "q_norm" in params:
        q = rmsnorm(q, params["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, params["k_norm"], cfg.norm_eps)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_chunk_append(x: jax.Array, params: dict, cfg: ModelConfig, *,
                           k_cache: jax.Array, v_cache: jax.Array,
                           kv_positions: jax.Array, positions: jax.Array,
                           reset: jax.Array):
    """Append a prompt chunk to a dense KV cache and attend.

    x [B,C,D] chunk tokens' hidden states; caches [B,T,KV,Dh]; positions
    [B,C] the chunk's absolute positions (``PAD_POS`` on pads — their cache
    writes are out-of-bounds scatters XLA drops); reset [B] bool — True on
    a request's *first* chunk, clearing the slot row's stale positions so
    a recycled slot's junk can never pass the positional mask as phantoms.

    The chunk's K/V are written before attending, so every query sees the
    prefix cached by earlier chunks plus the chunk itself causally (the
    per-query positional mask in ``_jnp_decode_attend``).  Non-SWA only:
    write indices are absolute positions (the capability gate
    ``supports_chunked_prefill`` rules ring buffers out).

    Returns (y [B,C,D], k_cache', v_cache', kv_positions').
    """
    B = x.shape[0]
    q, k_new, v_new = _project_chunk_kv(x, params, cfg, positions)

    b = jnp.arange(B)[:, None]
    with jax.named_scope("kv_update"):
        kv_positions = jnp.where(reset[:, None], -1, kv_positions)
        k_cache = k_cache.at[b, positions].set(k_new)
        v_cache = v_cache.at[b, positions].set(v_new)
        kv_positions = kv_positions.at[b, positions].set(positions)

    out = _jnp_decode_attend(q, k_cache, v_cache, kv_positions, positions,
                             cfg)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return y, k_cache, v_cache, kv_positions


def attention_chunk_append_paged(x: jax.Array, params: dict,
                                 cfg: ModelConfig, *,
                                 k_pool: jax.Array, v_pool: jax.Array,
                                 pos_pool: jax.Array,
                                 block_table: jax.Array,
                                 write_bids: jax.Array,
                                 positions: jax.Array,
                                 k_scale_pool: Optional[jax.Array] = None,
                                 v_scale_pool: Optional[jax.Array] = None):
    """Append a prompt chunk to a *paged* KV pool and attend.

    x [B,C,D]; pools [N,bs,KV,Dh] / pos_pool [N,bs]; block_table [B,M] the
    chunk owner's chain; write_bids [B,C] per-token destination blocks —
    TRASH for pads *and* for shared prefix blocks (content-cache hits were
    already written by their first owner; skipping the write is what makes
    sharing safe).  Block offsets are ``positions % bs``; a token landing
    at offset 0 of a fresh block first clears that block's position row
    (recycled storage — same contract as the one-token paged decode).

    Quantized pools (``k_scale_pool``/``v_scale_pool`` f32 [N,KV]): the
    chunk's K/V are quantized against their destination blocks'
    per-(block, kv-head) scales before the scatter (growing + in-place
    requantization via :func:`_quantized_block_write`) and the
    gather-attend dequantizes — same contract as
    :func:`attention_decode_paged`.

    Returns (y [B,C,D], k_pool', v_pool', pos_pool') — with the updated
    scale pools appended when quantized.
    """
    B = x.shape[0]
    bs = k_pool.shape[1]
    M = block_table.shape[1]
    KV, Dh = cfg.num_kv_heads, cfg.head_dim
    quantized = k_scale_pool is not None
    q, k_new, v_new = _project_chunk_kv(x, params, cfg, positions)

    off = (positions % bs).astype(jnp.int32)                    # [B,C]
    # clear fresh blocks' stale position rows before any chunk write; pads
    # and shared blocks carry TRASH write_bids, so their "clear" hits the
    # trash block (unobservable); tokens past offset 0 redirect their clear
    # there too (TRASH_BLOCK = 1, serve/blockpool.py)
    clear = jnp.where(off == 0, write_bids, jnp.ones_like(write_bids))
    with jax.named_scope("kv_update"):
        pos_pool = pos_pool.at[clear].set(-1)
        if quantized:
            k_pool, k_scale_pool = _quantized_block_write(
                k_pool, k_scale_pool, k_new, write_bids, off)
            v_pool, v_scale_pool = _quantized_block_write(
                v_pool, v_scale_pool, v_new, write_bids, off)
        else:
            k_pool = k_pool.at[write_bids, off].set(k_new)
            v_pool = v_pool.at[write_bids, off].set(v_new)
        pos_pool = pos_pool.at[write_bids, off].set(positions)

    flat = block_table.reshape(-1)
    if quantized:
        k = _dequantize_gather(k_pool, k_scale_pool, flat, x.dtype,
                               (B, M * bs, KV, Dh))
        v = _dequantize_gather(v_pool, v_scale_pool, flat, x.dtype,
                               (B, M * bs, KV, Dh))
    else:
        k = k_pool[flat].reshape(B, M * bs, KV, Dh)
        v = v_pool[flat].reshape(B, M * bs, KV, Dh)
    kvp = pos_pool[flat].reshape(B, M * bs)
    out = _jnp_decode_attend(q, k, v, kvp, positions, cfg)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    if quantized:
        return y, k_pool, v_pool, pos_pool, k_scale_pool, v_scale_pool
    return y, k_pool, v_pool, pos_pool
