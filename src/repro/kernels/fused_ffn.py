"""Fused SwiGLU FFN as a differentiable Pallas TPU kernel.

y = (silu(x @ Wg) * (x @ Wu)) @ Wd, fused so the [N, F] hidden activations
never round-trip HBM: the grid walks (row-block, F-block) with the F-block
axis minor; each step computes a [br, bf] hidden tile and accumulates its
contribution to the [br, D] output in VMEM scratch (emitted on the last
F step).  Matmuls run in the operands' dtype with f32 accumulation.  Each
call sizes Mosaic's scoped-VMEM limit from its own blocks (double-buffered
x/weight/output tiles + f32 scratch): at d_model=2560, bf=512 the forward
needs ~23 MiB, above the 16 MiB default.  The F block adapts to the hidden
width (the largest 128-multiple <= 512 dividing it), so a d_ff sharded
four ways (9728/4 = 2432) still tiles.

The op carries a ``jax.custom_vjp`` whose backward *reuses the forward
tiles*: nothing [N, F]-shaped is stashed as a residual — each backward
kernel recomputes the (g, u, h) tile it needs from (x, Wg, Wu) and folds it
straight into the gradient accumulators:

* ``_bwd_dx_kernel`` — same grid order as the forward (rows outer, F minor);
  accumulates dX = dG·Wgᵀ + dU·Wuᵀ in VMEM scratch, emitted on the last
  F step.
* ``_bwd_dw_kernel`` — transposed grid (F outer, rows minor) so each weight
  tile's accumulator sees its row contributions consecutively; emits
  dWg/dWu/dWd tiles on the last row step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BR = 256
DEFAULT_BF = 512
# v5e has 128 MiB of VMEM per core but Mosaic's default scoped limit is
# 16 MiB; a d_model=2560 tile set needs more, so every call states its need
VMEM_CAP = 100 * 2 ** 20


def pick_block(n: int, target: int, align: int = 128) -> int:
    """Largest block <= ``target`` that tiles ``n`` exactly: ``n`` itself
    when it fits, ``target`` when it divides, else the largest multiple of
    ``align`` dividing ``n`` (None when there is none — the caller falls
    back to the jnp path)."""
    if n <= target:
        return n
    if n % target == 0:
        return target
    b = target - target % align
    while b >= align and n % b:
        b -= align
    return b if b >= align else None


def blocks_ok(n_rows: int, d_ff: int) -> bool:
    """Whether the kernel's grid can tile an [n_rows, D] x [D, d_ff] call."""
    return (pick_block(n_rows, DEFAULT_BR, 8) is not None
            and pick_block(d_ff, DEFAULT_BF) is not None)


def _params(block_bytes: int, scratch_bytes: int, tile_bytes: int):
    """Mosaic params sized from the call's blocks: every pipelined block is
    double-buffered, plus scratch accumulators and the f32 hidden tiles."""
    need = 2 * block_bytes + scratch_bytes + 8 * tile_bytes + (4 << 20)
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(min(max(need, 16 << 20), VMEM_CAP)))


def _hidden_tile(x, wg_ref, wu_ref):
    """Recompute one [br, bf] forward tile: returns (g, sg, u) f32 where
    ``sg = logistic(g)`` so callers get silu(g) = g*sg and its derivative."""
    g = _dot(x, wg_ref[...], ((1,), (0,)))
    u = _dot(x, wu_ref[...], ((1,), (0,)))
    return g, jax.lax.logistic(g), u


def _dot(a, b, contract):
    """MXU matmul in the operands' dtype with f32 accumulation (an f32
    tile meeting a narrower weight tile is cast down to it first)."""
    dt = b.dtype if jnp.dtype(b.dtype).itemsize < 4 else a.dtype
    return jax.lax.dot_general(a.astype(dt), b.astype(dt),
                               (contract, ((), ())),
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _ffn_kernel(x_ref, wg_ref, wu_ref, wd_ref, y_ref, acc_ref):
    """Grid (n_rows//br, F//bf).  x_ref [br,D]; wg/wu_ref [D,bf];
    wd_ref [bf,D]; y_ref [br,D]; scratch acc [br,D] f32."""
    j = pl.program_id(1)
    nf = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g, sg, u = _hidden_tile(x_ref[...], wg_ref, wu_ref)
    h = (g * sg) * u                                     # silu(g) * u
    acc_ref[...] += _dot(h, wd_ref[...], ((1,), (0,)))

    @pl.when(j == nf - 1)
    def _emit():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def _nbytes(shape, dtype) -> int:
    n = jnp.dtype(dtype).itemsize
    for d in shape:
        n *= d
    return n


def _forward(x, w_gate, w_up, w_down, br, bf, interpret):
    N, D = x.shape
    F = w_gate.shape[1]
    blocks = (2 * _nbytes((br, D), x.dtype)
              + 3 * _nbytes((D, bf), w_gate.dtype))
    return pl.pallas_call(
        _ffn_kernel,
        name="swiglu_ffn",
        grid=(N // br, F // bf),
        in_specs=[
            pl.BlockSpec((br, D), lambda i, j: (i, 0)),
            pl.BlockSpec((D, bf), lambda i, j: (0, j)),
            pl.BlockSpec((D, bf), lambda i, j: (0, j)),
            pl.BlockSpec((bf, D), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((br, D), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((br, D), jnp.float32)],
        compiler_params=_params(blocks, _nbytes((br, D), jnp.float32),
                                _nbytes((br, bf), jnp.float32)),
        interpret=interpret,
    )(x, w_gate, w_up, w_down)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _bwd_hidden_grads(x, dy, wg_ref, wu_ref, wd_ref):
    """Shared backward tile math: recompute (g, u), push dy through Wd and
    the SwiGLU gate.  Returns (h, dg, du) f32 tiles [br, bf]."""
    g, sg, u = _hidden_tile(x, wg_ref, wu_ref)
    silu = g * sg
    h = silu * u
    dh = _dot(dy, wd_ref[...], ((1,), (1,)))              # [br,bf]
    du = dh * silu
    dg = dh * u * (sg + g * sg * (1.0 - sg))              # d silu / dg
    return h, dg, du


def _bwd_dx_kernel(x_ref, wg_ref, wu_ref, wd_ref, dy_ref, dx_ref, acc_ref):
    """Grid (n_rows//br, F//bf), F minor: dX accumulated over F tiles."""
    j = pl.program_id(1)
    nf = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _, dg, du = _bwd_hidden_grads(x_ref[...], dy_ref[...],
                                  wg_ref, wu_ref, wd_ref)
    acc_ref[...] += (_dot(dg, wg_ref[...], ((1,), (1,)))
                     + _dot(du, wu_ref[...], ((1,), (1,))))

    @pl.when(j == nf - 1)
    def _emit():
        dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)


def _bwd_dw_kernel(x_ref, wg_ref, wu_ref, wd_ref, dy_ref,
                   dwg_ref, dwu_ref, dwd_ref,
                   dwg_acc, dwu_acc, dwd_acc):
    """Grid (F//bf, n_rows//br), rows minor: weight-tile grads accumulated
    over row blocks (each output tile sees its revisits consecutively)."""
    i = pl.program_id(1)
    nr = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        dwg_acc[...] = jnp.zeros_like(dwg_acc)
        dwu_acc[...] = jnp.zeros_like(dwu_acc)
        dwd_acc[...] = jnp.zeros_like(dwd_acc)

    x = x_ref[...]
    dy = dy_ref[...]
    h, dg, du = _bwd_hidden_grads(x, dy, wg_ref, wu_ref, wd_ref)
    dwg_acc[...] += _dot(x, dg.astype(x.dtype), ((0,), (0,)))
    dwu_acc[...] += _dot(x, du.astype(x.dtype), ((0,), (0,)))
    dwd_acc[...] += _dot(h.astype(dy.dtype), dy, ((0,), (0,)))

    @pl.when(i == nr - 1)
    def _emit():
        dwg_ref[...] = dwg_acc[...].astype(dwg_ref.dtype)
        dwu_ref[...] = dwu_acc[...].astype(dwu_ref.dtype)
        dwd_ref[...] = dwd_acc[...].astype(dwd_ref.dtype)


def _backward(x, w_gate, w_up, w_down, dy, br, bf, interpret):
    N, D = x.shape
    F = w_gate.shape[1]
    row = _nbytes((br, D), x.dtype)
    wblk = _nbytes((D, bf), w_gate.dtype)
    tile = _nbytes((br, bf), jnp.float32)

    dx = pl.pallas_call(
        _bwd_dx_kernel,
        name="swiglu_ffn_bwd_dx",
        grid=(N // br, F // bf),
        in_specs=[
            pl.BlockSpec((br, D), lambda i, j: (i, 0)),
            pl.BlockSpec((D, bf), lambda i, j: (0, j)),
            pl.BlockSpec((D, bf), lambda i, j: (0, j)),
            pl.BlockSpec((bf, D), lambda i, j: (j, 0)),
            pl.BlockSpec((br, D), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, D), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((br, D), jnp.float32)],
        compiler_params=_params(3 * row + 3 * wblk,
                                _nbytes((br, D), jnp.float32), tile),
        interpret=interpret,
    )(x, w_gate, w_up, w_down, dy)

    dwg, dwu, dwd = pl.pallas_call(
        _bwd_dw_kernel,
        name="swiglu_ffn_bwd_dw",
        grid=(F // bf, N // br),
        in_specs=[
            pl.BlockSpec((br, D), lambda j, i: (i, 0)),
            pl.BlockSpec((D, bf), lambda j, i: (0, j)),
            pl.BlockSpec((D, bf), lambda j, i: (0, j)),
            pl.BlockSpec((bf, D), lambda j, i: (j, 0)),
            pl.BlockSpec((br, D), lambda j, i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((D, bf), lambda j, i: (0, j)),
            pl.BlockSpec((D, bf), lambda j, i: (0, j)),
            pl.BlockSpec((bf, D), lambda j, i: (j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((D, F), w_gate.dtype),
            jax.ShapeDtypeStruct((D, F), w_up.dtype),
            jax.ShapeDtypeStruct((F, D), w_down.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((D, bf), jnp.float32),
                        pltpu.VMEM((D, bf), jnp.float32),
                        pltpu.VMEM((bf, D), jnp.float32)],
        compiler_params=_params(2 * row + 6 * wblk,
                                3 * _nbytes((D, bf), jnp.float32), tile),
        interpret=interpret,
    )(x, w_gate, w_up, w_down, dy)
    return dx, dwg, dwu, dwd


# ---------------------------------------------------------------------------
# custom_vjp wiring
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _swiglu(x, w_gate, w_up, w_down, br, bf, interpret):
    return _forward(x, w_gate, w_up, w_down, br, bf, interpret)


def _swiglu_fwd(x, w_gate, w_up, w_down, br, bf, interpret):
    y = _forward(x, w_gate, w_up, w_down, br, bf, interpret)
    return y, (x, w_gate, w_up, w_down)


def _swiglu_bwd(br, bf, interpret, res, dy):
    x, w_gate, w_up, w_down = res
    return _backward(x, w_gate, w_up, w_down, dy, br, bf, interpret)


_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


@functools.partial(jax.jit, static_argnames=("br", "bf", "interpret"))
def swiglu_ffn(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
               w_down: jax.Array, *, br: int = DEFAULT_BR,
               bf: int = DEFAULT_BF, interpret: bool = True) -> jax.Array:
    """x [N,D]; w_gate/w_up [D,F]; w_down [F,D] -> [N,D].  Differentiable
    (``jax.custom_vjp``: backward recomputes the forward tiles)."""
    N, D = x.shape
    F = w_gate.shape[1]
    br = pick_block(N, br, 8)
    bf = pick_block(F, bf)
    assert br is not None and bf is not None, (N, F)
    return _swiglu(x, w_gate, w_up, w_down, br, bf, interpret)
