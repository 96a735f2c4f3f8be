"""Chunkwise-parallel mLSTM as a Pallas TPU kernel.

Implements exactly the chunk math of ``models/ssm.py::_mlstm_chunk`` (see
the derivation there): the grid is (batch, head, chunk); the chunk axis is
minor, so TPU runs it sequentially per (b,h) and the recurrent carry
(C [dh,dh], n [dh], m [1]) lives in VMEM scratch between chunk steps.  The
[L,L] intra-chunk score block and the rank-dh carry matmuls all stay in
VMEM — HBM sees only the [S,dh] streams, which is what makes mLSTM
training compute-bound instead of memory-bound on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mlstm_kernel(q_ref, k_ref, v_ref, i_ref, f_ref, y_ref,
                  C_ref, n_ref, m_ref, *, L: int):
    """Grid (B, H, nc).  q/k/v_ref [L,dh]; i/f_ref [L]; y_ref [L,dh];
    scratch C [dh,dh], n [dh], m [1,1]."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        C_ref[...] = jnp.zeros_like(C_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)

    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    i_gate = i_ref[...].astype(jnp.float32)                  # [L]
    f_log = f_ref[...].astype(jnp.float32)

    g = jnp.cumsum(f_log)                                    # [L]
    a = i_gate - g
    m_prev = m_ref[0, 0]
    M = jnp.maximum(jax.lax.cummax(a, axis=0), m_prev)       # [L]

    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [L,L]
    w = jnp.exp(a[None, :] - M[:, None])
    t_idx = jax.lax.iota(jnp.int32, L)
    causal = t_idx[None, :] <= t_idx[:, None]
    scores = jnp.where(causal, scores * w, 0.0)

    C_prev, n_prev = C_ref[...], n_ref[...]
    inter = jnp.exp(m_prev - M)                              # [L]
    y_num = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ()))) \
        + inter[:, None] * jax.lax.dot_general(
            q, C_prev, (((1,), (1,)), ((), ())))             # q · C^T rows
    d_t = jnp.sum(scores, axis=1) + inter * (q @ n_prev)
    y_ref[...] = (y_num / jnp.maximum(jnp.abs(d_t), 1.0)[:, None]
                  ).astype(y_ref.dtype)

    # carry update
    M_L, g_L = M[L - 1], g[L - 1]
    wc = jnp.exp(a - M_L)                                    # [L]
    C_ref[...] = (jax.lax.dot_general(v * wc[:, None], k,
                                      (((0,), (0,)), ((), ())))
                  + jnp.exp(m_prev - M_L) * C_prev)
    n_ref[...] = (wc @ k) + jnp.exp(m_prev - M_L) * n_prev
    m_ref[0, 0] = g_L + M_L


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_scan(q: jax.Array, k: jax.Array, v: jax.Array,
               i_gate: jax.Array, f_log: jax.Array, *,
               chunk: int = 256, interpret: bool = True) -> jax.Array:
    """q/k/v [B,H,S,dh] (k pre-scaled by dh^-0.5); i_gate/f_log [B,H,S]
    (f already log-sigmoid) -> y [B,H,S,dh]."""
    B, H, S, dh = q.shape
    L = min(chunk, S)
    assert S % L == 0, (S, L)
    nc = S // L

    kernel = functools.partial(_mlstm_kernel, L=L)
    return pl.pallas_call(
        kernel,
        name="mlstm_scan",
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((None, None, L, dh), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, L, dh), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, L, dh), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, L), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((None, None, L), lambda b, h, c: (b, h, c)),
        ],
        out_specs=pl.BlockSpec((None, None, L, dh),
                               lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((dh, dh), jnp.float32),
            pltpu.VMEM((dh,), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, i_gate, f_log)
