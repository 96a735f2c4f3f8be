"""Paged single-token decode attention as a Pallas TPU kernel.

The dense flash-decode kernel (decode_attention.py) streams a contiguous
[T]-long KV cache; this kernel streams a *paged* one: K/V live in a pooled
``[num_blocks, block_size, KV, Dh]`` tensor shared by every sequence, and
each query row follows its int32 block table ``[B, max_blocks]`` through the
pool.  The grid is (batch, table-column) with the table column as the
*minor* axis, so TPU executes one pool block per step per row and the
online-softmax state (m, l, acc) lives in VMEM scratch across those steps —
exactly the dense kernel's structure (and its shared per-head step), with
the block index indirected through a scalar-prefetched table
(``pltpu.PrefetchScalarGridSpec``: the table is resident before the kernel
body runs, so the DMA for step j can be issued from ``table[b, j]``).  Each
step's tile is the whole ``(bs, KV, D)`` pool block — one contiguous DMA —
and the kernel loops the kv-heads inside.

Masking is purely positional, which subsumes every tail case: ``pos_pool``
carries each pool entry's absolute position (-1 = never written), so the
partially-filled tail block of a sequence, the permanently-empty null block
that unused table entries point at, and entries past the query's position
all mask out identically.  GQA blocks all G = H/KV q-heads of a kv-head
into one [G, D] tile, as in the dense kernel.

No sliding-window variant: SWA archs keep the dense ring buffer (the
registry's ``supports_paged_decode`` excludes them).

The quantized variant (:func:`paged_decode_attention_q8`) streams int8
pools plus per-(block, kv-head) f32 scales ``[N, KV]`` and dequantizes
each tile *in-loop* in VMEM — the scale row rides the same block-table
indirection as the K/V tiles, so full-precision KV never exists in HBM;
it is reconstructed one [bs, D] tile at a time inside the online-softmax
loop.  Entry positions and scale rows are viewed as ``[N, 1, bs]`` /
``[N, 1, KV]`` so each step's tile spans the array's full trailing dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import decode_attention as _da


def _paged_step(b, j, pos_ref, q_ref, k_ref, v_ref, kvp_ref, o_ref,
                m_ref, l_ref, acc_ref, scale, k_scale=None, v_scale=None):
    """One (row, table-column) step: init at j == 0, fold the pool block
    in, emit at the last column.  Shared by the f32 and int8 kernels."""
    @pl.when(j == 0)
    def _init():
        _da.init_scratch(m_ref, l_ref, acc_ref)

    kv_pos = kvp_ref[...]                                        # [1,bs]
    valid = (kv_pos >= 0) & (kv_pos <= pos_ref[b])
    _da.online_softmax_step(q_ref, k_ref, v_ref, valid, m_ref, l_ref,
                            acc_ref, scale=scale, k_scale=k_scale,
                            v_scale=v_scale)

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        _da.emit(o_ref, l_ref, acc_ref)


def _paged_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, kvp_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale: float):
    """Grid (B, M).  q_ref [KV,G,D]; k_ref/v_ref [bs,KV,D] (the pool block
    the table's (b, j) entry selects); kvp_ref [1,bs]; tbl_ref/pos_ref are
    scalar-prefetched; scratch m/l [KV,G,1], acc [KV,G,D]."""
    _paged_step(pl.program_id(0), pl.program_id(1), pos_ref, q_ref, k_ref,
                v_ref, kvp_ref, o_ref, m_ref, l_ref, acc_ref, scale)


def _paged_q8_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                     kvp_ref, o_ref, m_ref, l_ref, acc_ref, *, scale: float):
    """int8 variant: k_ref/v_ref are int8 [bs,KV,D] tiles and ks_ref/vs_ref
    the block's per-kv-head f32 scale row [1,KV]; dequant happens here, in
    VMEM, inside the loop — HBM only ever holds the quantized pool."""
    _paged_step(pl.program_id(0), pl.program_id(1), pos_ref, q_ref, k_ref,
                v_ref, kvp_ref, o_ref, m_ref, l_ref, acc_ref, scale,
                k_scale=ks_ref[...], v_scale=vs_ref[...])


def _paged_call(kernel, name, q, pools, extra_rows, pos_pool, block_table,
                pos, interpret):
    """Shared pallas_call plumbing: ``pools`` are the [N,bs,KV,D] K/V pools,
    ``extra_rows`` per-block rows (scales) viewed as [N,1,n]; ``name`` is
    the kernel's name in the compiled program."""
    B, H, D = q.shape
    N, bs, KV = pools[0].shape[:3]
    M = block_table.shape[1]
    G = H // KV

    def blk(i, j, tbl, pos):
        return (tbl[i, j], 0, 0)

    pool_spec = pl.BlockSpec((None, bs, KV, D),
                             lambda i, j, tbl, pos: (tbl[i, j], 0, 0, 0))
    row_specs = [pl.BlockSpec((None, 1, r.shape[-1]), blk)
                 for r in extra_rows]
    head_spec = pl.BlockSpec((None, KV, G, D),
                             lambda i, j, tbl, pos: (i, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # block_table, pos
        grid=(B, M),
        in_specs=[head_spec, pool_spec, pool_spec, *row_specs,
                  pl.BlockSpec((None, 1, bs), blk)],
        out_specs=head_spec,
        scratch_shapes=_da.scratch_shapes(KV, G, D),
    )
    out = pl.pallas_call(
        functools.partial(kernel, scale=D ** -0.5),
        name=name,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), pos.astype(jnp.int32),
      q.reshape(B, KV, G, D), *pools,
      *(r.reshape(N, 1, r.shape[-1]) for r in extra_rows),
      pos_pool.reshape(N, 1, bs))
    return out.reshape(B, H, D)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, pos_pool: jax.Array,
                           block_table: jax.Array, pos: jax.Array, *,
                           interpret: bool = True) -> jax.Array:
    """q [B,H,D]; k_pool/v_pool [N,bs,KV,D] (grouped heads);
    pos_pool [N,bs] int32 (-1 = empty); block_table [B,M] int32;
    pos [B] int32 -> [B,H,D]."""
    return _paged_call(_paged_kernel, "paged_decode_attention", q,
                       (k_pool, v_pool), (), pos_pool, block_table, pos,
                       interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_q8(q: jax.Array, k_pool: jax.Array,
                              v_pool: jax.Array, k_scale: jax.Array,
                              v_scale: jax.Array, pos_pool: jax.Array,
                              block_table: jax.Array, pos: jax.Array, *,
                              interpret: bool = True) -> jax.Array:
    """Quantized-pool decode: q [B,H,D]; k_pool/v_pool int8 [N,bs,KV,D];
    k_scale/v_scale f32 [N,KV] (per-(block, kv-head) max-abs scales);
    pos_pool [N,bs] int32 (-1 = empty); block_table [B,M] int32; pos [B]
    int32 -> [B,H,D].  The scales ride the same block-table indirection
    as the K/V tiles and dequant happens in-loop in VMEM."""
    return _paged_call(_paged_q8_kernel, "paged_decode_attention_q8", q,
                       (k_pool, v_pool), (k_scale, v_scale), pos_pool,
                       block_table, pos, interpret)
