"""int8 block quantization as a Pallas TPU kernel.

The wire format of the compressed cross-pod gradient sync
(core/compression.py): payloads are flattened into blocks of 256 values
with one f32 max-abs scale per block.  The kernel tiles rows of blocks
through VMEM; quantize and dequantize are separate kernels so the wire
format (int8 + scales) is a real boundary, exactly what crosses the slow
tier in the paper's terms.

The same per-block max-abs math backs the quantized paged KV cache
(serve/blockpool.py): :func:`block_quant` / :func:`block_dequant` are the
pure-jnp form, quantizing over the *last* axis of an arbitrary-rank
tensor so the pool write path (one [KV, Dh] tile per written token) and
the ref oracle share one definition with the Pallas kernels here.

``interpret`` resolves from the backend (ops selection policy) when left
as None, like every other kernel — the jitted entry points take the
resolved bool as a static arg.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 256
ROWS = 64          # quantization blocks per grid step


def _resolve_interpret(interpret):
    if interpret is None:
        from repro.kernels import ops
        return ops._interpret()
    return bool(interpret)


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)               # [ROWS, BLOCK]
    scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0  # [ROWS, 1]
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / safe), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def block_quant(x: jax.Array):
    """Max-abs int8 quantization over the last axis (pure jnp).

    x [..., D] -> (q int8 [..., D], scale f32 [...]) with
    ``scale = max|x| / 127`` per leading index and all-zero rows mapping
    to scale 0 (no NaN).  Same math as ``_quant_kernel``; shared by the
    quantized KV pool's write path and the ref dequant oracle.
    """
    x = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=-1) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / safe[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def block_dequant(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`block_quant`: int8 [..., D] × f32 [...] -> f32."""
    return q.astype(jnp.float32) * scale[..., None]


@functools.partial(jax.jit, static_argnames=("block", "rows", "interpret"))
def _quantize_int8(x, *, block, rows, interpret):
    nb = x.shape[0]
    rows = min(rows, nb)
    assert nb % rows == 0 and x.shape[1] == block
    q, s = pl.pallas_call(
        _quant_kernel,
        name="_quantize_int8",
        grid=(nb // rows,),
        in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb, block), jnp.int8),
                   jax.ShapeDtypeStruct((nb, 1), jnp.float32)],
        interpret=interpret,
    )(x)
    return q, s[:, 0]


def quantize_int8(x: jax.Array, *, block: int = BLOCK, rows: int = ROWS,
                  interpret=None):
    """x [n_blocks, block] f32 -> (q int8 same shape, scale [n_blocks]).

    ``interpret=None`` resolves from the backend (compiled on TPU,
    interpreted elsewhere) before entering the jitted kernel wrapper.
    """
    return _quantize_int8(x, block=block, rows=rows,
                          interpret=_resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def _dequantize_int8(q, scale, *, rows, interpret):
    nb, block = q.shape
    rows = min(rows, nb)
    assert nb % rows == 0
    return pl.pallas_call(
        _dequant_kernel,
        name="_dequantize_int8",
        grid=(nb // rows,),
        in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0)),
                  pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block), jnp.float32),
        interpret=interpret,
    )(q, scale[:, None])


def dequantize_int8(q: jax.Array, scale: jax.Array, *, rows: int = ROWS,
                    interpret=None) -> jax.Array:
    """Inverse of :func:`quantize_int8`; interpret resolves like there."""
    return _dequantize_int8(q, scale, rows=rows,
                            interpret=_resolve_interpret(interpret))
