"""Single-token decode attention (flash-decode) as a Pallas TPU kernel.

One new token attends to a [T]-long KV cache.  The grid is
(batch, kv-block); the kv-block axis is the *minor* grid dim, so TPU
executes it sequentially per row and the online-softmax state (m, l, acc)
lives in VMEM scratch across those steps — the kernel never materializes
the [T] score vector in HBM.  Each step's K/V tile spans every KV head —
``(bk, KV, D)`` is the cache's own trailing layout, so the DMA is one
contiguous slab — and the kernel loops the heads inside.  GQA is handled
by blocking all G = H/KV q-heads of a kv-head into one [G, D] tile (they
share the same K/V stream, so the MXU sees a [G,D]x[D,bk] matmul instead
of G vector products — the decode-bandwidth win TPUs need).  The query
positions ride scalar prefetch (SMEM); the cache's entry positions are
viewed as [B, 1, T] so their tiles are lane-dense.

Ring-buffer caches (SWA) work unchanged: masking is positional
(``kv_pos`` carries absolute positions, -1 = empty slot).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BK = 512


def online_softmax_step(q_ref, k_ref, v_ref, valid, m_ref, l_ref, acc_ref,
                        *, scale: float, k_scale=None, v_scale=None):
    """Fold one [bk]-entry K/V tile into the per-head (m, l, acc) scratch.

    q_ref [KV,G,D]; k_ref/v_ref [bk,KV,D]; valid [1,bk] bool; scratch
    m/l [KV,G,1], acc [KV,G,D].  ``k_scale``/``v_scale`` (optional, [1,KV]
    f32) dequantize int8 tiles per kv-head.  Shared by the dense and paged
    decode kernels — they differ only in where the tile comes from."""
    KV = q_ref.shape[0]
    for h in range(KV):
        q = q_ref[h].astype(jnp.float32) * scale                 # [G,D]
        kb = k_ref[:, h, :].astype(jnp.float32)                  # [bk,D]
        vb = v_ref[:, h, :].astype(jnp.float32)
        if k_scale is not None:
            kb = kb * _lane(k_scale, h)
            vb = vb * _lane(v_scale, h)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())))  # [G,bk]
        s = jnp.where(valid, s, NEG_INF)
        m_prev, l_prev = m_ref[h], l_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_ref[h] = m_new
        l_ref[h] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())))


def _lane(row, h: int):
    """Column ``h`` of a [1,n] row as a [1,1] value (masked lane sum: no
    scalar extraction from vector registers)."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lanes == h, row, 0.0), axis=1, keepdims=True)


def init_scratch(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def emit(o_ref, l_ref, acc_ref):
    o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                  ).astype(o_ref.dtype)


def scratch_shapes(KV: int, G: int, D: int):
    return [pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, D), jnp.float32)]


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, kvp_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale: float, window: int):
    """Grid (B, T//bk).  pos_ref [B] (scalar prefetch); q_ref [KV,G,D];
    k_ref/v_ref [bk,KV,D]; kvp_ref [1,bk]; o_ref [KV,G,D]."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        init_scratch(m_ref, l_ref, acc_ref)

    pos = pos_ref[b]
    kv_pos = kvp_ref[...]                                        # [1,bk]
    valid = (kv_pos >= 0) & (kv_pos <= pos)
    if window > 0:
        valid &= kv_pos > (pos - window)
    online_softmax_step(q_ref, k_ref, v_ref, valid, m_ref, l_ref, acc_ref,
                        scale=scale)

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        emit(o_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("window", "bk", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_pos: jax.Array, pos: jax.Array, *,
                     window: int = 0, bk: int = DEFAULT_BK,
                     interpret: bool = True) -> jax.Array:
    """q [B,H,D]; k/v [B,T,KV,D] (grouped heads); kv_pos [B,T] int32;
    pos [B] int32 -> [B,H,D]."""
    B, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    bk = min(bk, T)
    while T % bk:        # shrink to a divisor (serve capacities vary)
        bk //= 2
    assert bk >= 1, (T, bk)
    scale = D ** -0.5

    qg = q.reshape(B, KV, G, D)
    kernel = functools.partial(_decode_kernel, scale=scale, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,           # pos
        grid=(B, T // bk),
        in_specs=[
            pl.BlockSpec((None, KV, G, D), lambda b, j, pos: (b, 0, 0, 0)),
            pl.BlockSpec((None, bk, KV, D), lambda b, j, pos: (b, j, 0, 0)),
            pl.BlockSpec((None, bk, KV, D), lambda b, j, pos: (b, j, 0, 0)),
            pl.BlockSpec((None, 1, bk), lambda b, j, pos: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, KV, G, D),
                               lambda b, j, pos: (b, 0, 0, 0)),
        scratch_shapes=scratch_shapes(KV, G, D),
    )
    out = pl.pallas_call(
        kernel,
        name="decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        interpret=interpret,
    )(pos.astype(jnp.int32), qg, k, v, kv_pos.reshape(B, 1, T))
    return out.reshape(B, H, D)
