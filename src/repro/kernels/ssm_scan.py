"""Fused selective-SSM (Mamba) chunk scan as a Pallas TPU kernel.

The memory hazard of Mamba training is the [B,S,Di,N] gate expansion
(a = exp(dt·A), b = dt·B·x).  The jnp path (models/ssm.py) bounds it per
chunk with remat; this kernel eliminates it from HBM entirely: the grid is
(batch, Di-block, chunk) with the chunk axis minor (sequential), the
[L, dblk, N] gates are built in VMEM from the dt/B/x streams, scanned
in-register, and only y [L, dblk] and the final h [dblk, N] ever leave.

This is the TPU adaptation of the Mamba paper's fused CUDA scan: where the
GPU version tiles over threadblocks with shared-memory prefix sums, the
TPU version rides the (8,128)-lane VPU with a log-depth associative scan
over the chunk axis and keeps the recurrent carry in VMEM scratch across
sequential grid steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _assoc(e1, e2):
    a1, b1 = e1
    a2, b2 = e2
    return a1 * a2, a2 * b1 + b2


def _ssm_kernel(dt_ref, bssm_ref, cssm_ref, x_ref, A_ref, y_ref, hout_ref,
                h_ref, *, L: int, N: int):
    """Grid (B, nd, nc).  dt/x_ref [L,dblk]; bssm/cssm_ref [L,N];
    A_ref [dblk,N]; y_ref [L,dblk]; hout_ref [dblk,N]; scratch h [dblk,N].
    """
    c = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(c == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    dt = dt_ref[...].astype(jnp.float32)                 # [L,dblk]
    x = x_ref[...].astype(jnp.float32)
    B_ssm = bssm_ref[...].astype(jnp.float32)            # [L,N]
    C_ssm = cssm_ref[...].astype(jnp.float32)
    A = A_ref[...].astype(jnp.float32)                   # [dblk,N]

    a = jnp.exp(dt[:, :, None] * A[None])                # [L,dblk,N]
    b = (dt * x)[:, :, None] * B_ssm[:, None, :]

    pa, pb = jax.lax.associative_scan(_assoc, (a, b), axis=0)
    h_t = pa * h_ref[...][None] + pb                     # [L,dblk,N]
    # y_t = C_t · h_t (contract N)
    y_ref[...] = jnp.einsum("ln,len->le", C_ssm, h_t).astype(y_ref.dtype)
    h_ref[...] = h_t[L - 1]

    @pl.when(c == nc - 1)
    def _emit():
        hout_ref[...] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "dblk", "interpret"))
def ssm_chunk_scan(dt: jax.Array, B_ssm: jax.Array, C_ssm: jax.Array,
                   x: jax.Array, A: jax.Array, *, chunk: int = 256,
                   dblk: int = 512, interpret: bool = True):
    """dt/x [B,S,Di] (dt already softplus'd, x post-conv); B_ssm/C_ssm
    [B,S,N]; A [Di,N] (negative).  Returns (y [B,S,Di], h [B,Di,N])."""
    B, S, Di = dt.shape
    N = A.shape[-1]
    L = min(chunk, S)
    dblk = min(dblk, Di)
    assert S % L == 0 and Di % dblk == 0, (S, L, Di, dblk)

    kernel = functools.partial(_ssm_kernel, L=L, N=N)
    y, h = pl.pallas_call(
        kernel,
        name="ssm_chunk_scan",
        grid=(B, Di // dblk, S // L),
        in_specs=[
            pl.BlockSpec((None, L, dblk), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((None, L, N), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((None, L, N), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((None, L, dblk), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((dblk, N), lambda b, d, c: (d, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, L, dblk), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((None, dblk, N), lambda b, d, c: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, Di), dt.dtype),
            jax.ShapeDtypeStruct((B, Di, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dblk, N), jnp.float32)],
        interpret=interpret,
    )(dt, B_ssm, C_ssm, x, A)
    return y, h
