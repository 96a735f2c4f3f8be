"""Flash attention (training/prefill) as a differentiable Pallas TPU kernel.

TPU adaptation of the standard flash blocking: the [S,T] score matrix never
leaves VMEM — the grid walks (batch, head, q-block) and an inner
``fori_loop`` streams K/V blocks through the MXU with an online softmax.
Causal masking skips whole KV blocks past the diagonal (the loop bound is
dynamic in the q-block index), which halves the FLOPs of a causal prefill
exactly like the chunked-jnp reference (models/attention.py) does at the
XLA level — but here the blocking is explicit VMEM tiling rather than a
compiler hint.

The op carries a ``jax.custom_vjp``: the forward additionally emits the
per-row logsumexp (``lse = m + log(l)``) as a residual, and the backward
recomputes the softmax probabilities from (q, k, lse) tile by tile — the
flash-attention-2 recipe — in two Pallas kernels:

* ``_bwd_dq_kernel``  — grid (b, h, q-block), streams KV blocks, accumulates
  dQ in VMEM (same causal block skipping as the forward).
* ``_bwd_dkv_kernel`` — grid (b, h, kv-block), streams Q blocks starting at
  the causal diagonal, accumulates dK/dV in VMEM.

Neither materializes the [S,T] probability matrix; the only O(S) residuals
are ``o`` and ``lse``.  ``delta = rowsum(do * o)`` is precomputed in jnp.

Block shapes: q rows BQ=256 (MXU-aligned: multiples of 128 for f32/bf16
tiles), KV block BK=512.  VMEM claim per grid step ≈
BQ·D + 2·T_BLOCK·D + BQ·BK (scores) floats — sized for D ≤ 256.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

DEFAULT_BQ = 256
DEFAULT_BK = 512


def _block_mask(q0, k0, bq: int, bk: int, causal: bool, window: int,
                transposed: bool = False):
    """[bq,bk] boolean ([bk,bq] when ``transposed``) for the tile whose
    first q row is ``q0`` and first kv column is ``k0``; True = attend.
    Mirrors models.attention._mask for standard arange positions.  Built
    from 2-D iotas (TPU has no 1-D iota)."""
    if not causal:
        return None
    shape, qd = ((bk, bq), 1) if transposed else ((bq, bk), 0)
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, qd)
    kv_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - qd)
    mask = kv_pos <= q_pos
    if window > 0:
        mask &= kv_pos > (q_pos - window)
    return mask


def _first_kv_block(iq, bq: int, bk: int, causal: bool, window: int):
    """First KV block not entirely below the sliding window of q block
    ``iq`` (0 without SWA): block skipping for the fwd/dq loops."""
    if not (causal and window > 0):
        return 0
    return jnp.maximum(0, (iq * bq - window + 1) // bk)


def _last_kv_block(iq, bq: int, bk: int, T: int, causal: bool):
    """One past the last KV block q block ``iq`` can see."""
    nkv = T // bk
    if causal:
        nkv = jnp.minimum(nkv, ((iq + 1) * bq - 1) // bk + 1)
    return nkv


def _dot(a, b, contract):
    """MXU matmul in the operands' dtype with f32 accumulation."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _row(col):
    """[n,1] column -> [1,n] row (lane-dense) via one aligned 2-D
    transpose; the softmax-stat residuals are stored as rows."""
    n = col.shape[0]
    return jnp.transpose(jnp.broadcast_to(col, (n, 128)))[:1]


def _col(row):
    """[1,n] row -> [n,1] column (inverse of :func:`_row`)."""
    n = row.shape[1]
    return jnp.transpose(jnp.broadcast_to(row, (8, n)))[:, :1]


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *lse_ref, bq: int, bk: int,
                 scale: float, causal: bool, window: int):
    """One (b, h, q-block) step.  q_ref [bq,d]; k_ref/v_ref [T,d] (streamed
    through the MXU in bk slices); o_ref [bq,d]; optional lse_ref [1,bq]
    (softmax-stats residual for the backward, stored lane-dense)."""
    iq = pl.program_id(2)
    T = k_ref.shape[0]
    d = q_ref.shape[-1]
    q = q_ref[...]

    def body(j, carry):
        m, l, acc = carry
        kb = k_ref[pl.ds(j * bk, bk), :]
        vb = v_ref[pl.ds(j * bk, bk), :]
        s = _dot(q, kb, ((1,), (1,))) * scale                    # [bq,bk]
        mask = _block_mask(iq * bq, j * bk, bq, bk, causal, window)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * corr + _dot(p.astype(vb.dtype), vb, ((1,), (0,)))
        return m_new, l_new, acc_new

    m0 = jnp.full((bq, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(
        _first_kv_block(iq, bq, bk, causal, window),
        _last_kv_block(iq, bq, bk, T, causal), body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    if lse_ref:
        # lse on the *scaled* scores; fully-masked rows (l == 0, never
        # produced by the model paths) get 0.0 so the backward's
        # exp(s - lse) stays 0
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), 0.0)
        lse_ref[0][...] = _row(lse)


def _forward(q, k, v, causal, window, bq, bk, interpret, with_lse=True):
    """Returns (out, lse) with lse [B,H,1,S] float32, or out alone when
    ``with_lse`` is False (inference never writes the residual)."""
    B, H, S, D = q.shape
    T = k.shape[2]
    scale = D ** -0.5
    kernel = functools.partial(_attn_kernel, bq=bq, bk=bk, scale=scale,
                               causal=causal, window=window)
    out_specs = [pl.BlockSpec((None, None, bq, D), lambda b, h, i: (b, h, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((B, H, S, D), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((None, None, 1, bq),
                                      lambda b, h, i: (b, h, 0, i)))
        out_shape.append(jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32))
    res = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=(B, H, S // bq),
        in_specs=[
            pl.BlockSpec((None, None, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((None, None, T, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((None, None, T, D), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(q, k, v)
    return res if with_lse else res[0]


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
                   bq: int, bk: int, scale: float, causal: bool, window: int):
    """dQ for one (b, h, q-block): stream KV blocks, recompute p from lse."""
    iq = pl.program_id(2)
    T = k_ref.shape[0]
    d = q_ref.shape[-1]
    q = q_ref[...]
    do = do_ref[...]
    lse = _col(lse_ref[...])                                     # [bq,1]
    delta = _col(delta_ref[...])

    def body(j, acc):
        kb = k_ref[pl.ds(j * bk, bk), :]
        vb = v_ref[pl.ds(j * bk, bk), :]
        s = _dot(q, kb, ((1,), (1,))) * scale                    # [bq,bk]
        p = jnp.exp(s - lse)
        mask = _block_mask(iq * bq, j * bk, bq, bk, causal, window)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = _dot(do, vb, ((1,), (1,)))                          # [bq,bk]
        ds = p * (dp - delta)
        return acc + _dot(ds.astype(kb.dtype), kb, ((1,), (0,)))

    acc = jax.lax.fori_loop(
        _first_kv_block(iq, bq, bk, causal, window),
        _last_kv_block(iq, bq, bk, T, causal), body,
        jnp.zeros((bq, d), jnp.float32))
    dq_ref[...] = (acc * scale).astype(dq_ref.dtype)


def _dkv_live(i, j, bq: int, bk: int, causal: bool, window: int):
    """Whether q block ``i`` contributes to kv block ``j``: on/after the
    causal diagonal and, with SWA, not past the window."""
    live = jnp.bool_(True)
    if causal:
        live &= i >= (j * bk) // bq
        if window > 0:
            # q rows with q_pos > max(kv_pos) + window - 1 are fully masked
            live &= i < ((j + 1) * bk + window - 2) // bq + 1
    return live


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, bq: int, bk: int,
                    scale: float, causal: bool, window: int):
    """dK/dV for one (b, h, kv-block, q-block) step.  The q block is the
    minor grid axis; dK/dV accumulate in VMEM scratch and are emitted on
    the last q block.  Works in the transposed [bk,bq] orientation so the
    lane-dense lse/delta rows broadcast without a transpose."""
    j = pl.program_id(2)
    i = pl.program_id(3)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_dkv_live(i, j, bq, bk, causal, window))
    def _step():
        kb = k_ref[...]
        vb = v_ref[...]
        qb = q_ref[...]
        dob = do_ref[...]
        st = _dot(kb, qb, ((1,), (1,))) * scale                  # [bk,bq]
        pt = jnp.exp(st - lse_ref[...])
        mask = _block_mask(i * bq, j * bk, bq, bk, causal, window,
                           transposed=True)
        if mask is not None:
            pt = jnp.where(mask, pt, 0.0)
        dv_acc[...] += _dot(pt.astype(dob.dtype), dob, ((1,), (0,)))
        dpt = _dot(vb, dob, ((1,), (1,)))                        # [bk,bq]
        dst = pt * (dpt - delta_ref[...])
        # s = (q·k)*scale, so ∂s/∂k is the *scaled* q rows
        dk_acc[...] += _dot(dst.astype(qb.dtype), qb, ((1,), (0,))) * scale

    @pl.when(i == pl.num_programs(3) - 1)
    def _emit():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _backward(q, k, v, o, lse, g, causal, window, bq, bk, interpret):
    B, H, S, D = q.shape
    T = k.shape[2]
    scale = D ** -0.5
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, :, None, :]                      # [B,H,1,S]

    dq_kernel = functools.partial(_bwd_dq_kernel, bq=bq, bk=bk, scale=scale,
                                  causal=causal, window=window)
    row_spec = pl.BlockSpec((None, None, 1, bq), lambda b, h, i: (b, h, 0, i))
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_attention_bwd_dq",
        grid=(B, H, S // bq),
        in_specs=[
            pl.BlockSpec((None, None, bq, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((None, None, T, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((None, None, T, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((None, None, bq, D), lambda b, h, i: (b, h, i, 0)),
            row_spec,
            row_spec,
        ],
        out_specs=pl.BlockSpec((None, None, bq, D),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        interpret=interpret,
    )(q, k, v, g, lse, delta)

    dkv_kernel = functools.partial(_bwd_dkv_kernel, bq=bq, bk=bk, scale=scale,
                                   causal=causal, window=window)
    q_spec = pl.BlockSpec((None, None, bq, D), lambda b, h, j, i: (b, h, i, 0))
    kv_spec = pl.BlockSpec((None, None, bk, D), lambda b, h, j, i: (b, h, j, 0))
    row_spec = pl.BlockSpec((None, None, 1, bq),
                            lambda b, h, j, i: (b, h, 0, i))
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_attention_bwd_dkv",
        grid=(B, H, T // bk, S // bq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, T, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wiring
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, bq, bk, interpret):
    return _forward(q, k, v, causal, window, bq, bk, interpret,
                    with_lse=False)


def _flash_fwd(q, k, v, causal, window, bq, bk, interpret):
    out, lse = _forward(q, k, v, causal, window, bq, bk, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, bq, bk, interpret, res, g):
    q, k, v, out, lse = res
    return _backward(q, k, v, out, lse, g, causal, window, bq, bk, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: bool = True) -> jax.Array:
    """q [B,H,S,D], k/v [B,H,T,D] -> [B,H,S,D].  Differentiable
    (``jax.custom_vjp``: flash backward with recomputed softmax stats).

    ``window > 0`` = sliding-window attention (mixtral); positions are the
    standard arange (causal masking compares absolute row/col indices).  On
    this container ``interpret=True`` runs the kernel body on CPU; on TPU
    pass False.
    """
    B, H, S, D = q.shape
    T = k.shape[2]
    bq = min(bq, S)
    bk = min(bk, T)
    assert S % bq == 0 and T % bk == 0, (S, bq, T, bk)
    return _flash(q, k, v, causal, window, bq, bk, interpret)
