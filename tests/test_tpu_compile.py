"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode runs the kernel bodies on the CPU and cannot see what the
TPU compiler refuses: block shapes off the (8, 128) tiling, more scoped
VMEM than a kernel may claim, rank-1 blocks.  These tests hand each kernel
to the real Mosaic compiler for one chip of a described ``v5e:2x2``
topology (nothing runs; only shapes are passed) at qwen3-4b widths:
H=32, KV=8, Dh=128, d_model=2560, d_ff=9728.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and under several
pytest workers the worker given this file is the one that loads it.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.topology import make_plan
from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import fused_ffn as ffn
from repro.kernels import ops
from repro.kernels import paged_attention as pa
from repro.kernels import quant
from repro.models.common import abstract_params
from repro.models.registry import model_specs
from repro.serve import kvcache
from repro.serve.steps import make_decode_step

H, KV, DH, D_MODEL, D_FF = 32, 8, 128, 2560, 9728
BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described-topology compile is written to the persistent cache but
    cannot be read back without a chip; keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)``: an abstract argument on one described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def _compile(fn, *args):
    """Compile for the described chip; the program must hold a Mosaic
    kernel (a silent jnp fallback would pass for the wrong reason)."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _flash_fwd(shape):
    q = shape((1, H, 2048, DH), BF16)
    return (lambda q, k, v: fa.flash_attention(q, k, v, interpret=False),
            (q, q, q))


def _flash_grad(shape):
    q = shape((1, H, 2048, DH), BF16)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v,
                                          interpret=False).astype(F32))
    return jax.grad(loss, argnums=(0, 1, 2)), (q, q, q)


def _ffn_args(shape, rows, d_ff=D_FF):
    return (shape((rows, D_MODEL), BF16), shape((D_MODEL, d_ff), BF16),
            shape((D_MODEL, d_ff), BF16), shape((d_ff, D_MODEL), BF16))


def _ffn_fwd(shape):
    return (lambda *a: ffn.swiglu_ffn(*a, interpret=False),
            _ffn_args(shape, 2048))


def _ffn_decode_rows(shape):
    return (lambda *a: ffn.swiglu_ffn(*a, interpret=False),
            _ffn_args(shape, 4))


def _ffn_fwd_tp4_shard(shape):
    """One device's share of the FFN on a 4-way model axis: 9728 / 4 =
    2432 columns, which only a 128-wide F block divides."""
    return (lambda *a: ffn.swiglu_ffn(*a, interpret=False),
            _ffn_args(shape, 2048, D_FF // 4))


def _ffn_grad(shape):
    def loss(*a):
        return jnp.sum(ffn.swiglu_ffn(*a, interpret=False).astype(F32))
    return jax.grad(loss, argnums=(0, 1, 2, 3)), _ffn_args(shape, 2048)


def _decode_dense(shape):
    B, T = 4, 4096
    return (lambda q, k, v, kp, p: da.decode_attention(q, k, v, kp, p,
                                                       interpret=False),
            (shape((B, H, DH), BF16), shape((B, T, KV, DH), BF16),
             shape((B, T, KV, DH), BF16), shape((B, T), I32),
             shape((B,), I32)))


_PAGED = dict(B=4, N=4 * 256 + 2, bs=16, M=256)   # 4 slots x 4096 tokens


def _paged_f32(shape):
    B, N, bs, M = (_PAGED[k] for k in ("B", "N", "bs", "M"))
    return (lambda q, k, v, pp, t, p: pa.paged_decode_attention(
                q, k, v, pp, t, p, interpret=False),
            (shape((B, H, DH), BF16), shape((N, bs, KV, DH), BF16),
             shape((N, bs, KV, DH), BF16), shape((N, bs), I32),
             shape((B, M), I32), shape((B,), I32)))


def _paged_q8(shape):
    B, N, bs, M = (_PAGED[k] for k in ("B", "N", "bs", "M"))
    return (lambda q, k, v, ks, vs, pp, t, p: pa.paged_decode_attention_q8(
                q, k, v, ks, vs, pp, t, p, interpret=False),
            (shape((B, H, DH), BF16), shape((N, bs, KV, DH), I8),
             shape((N, bs, KV, DH), I8), shape((N, KV), F32),
             shape((N, KV), F32), shape((N, bs), I32), shape((B, M), I32),
             shape((B,), I32)))


_QUANT_BLOCKS = 4096 * D_MODEL // quant.BLOCK     # one [4096, 2560] payload


def _quantize(shape):
    return (lambda x: quant.quantize_int8(x, interpret=False),
            (shape((_QUANT_BLOCKS, quant.BLOCK), F32),))


def _dequantize(shape):
    return (lambda q, s: quant.dequantize_int8(q, s, interpret=False),
            (shape((_QUANT_BLOCKS, quant.BLOCK), I8),
             shape((_QUANT_BLOCKS,), F32)))


CASES = {
    "flash_fwd_s2048": _flash_fwd,
    "flash_grad_s2048": _flash_grad,
    "ffn_fwd_n2048": _ffn_fwd,
    "ffn_fwd_n4": _ffn_decode_rows,
    "ffn_fwd_tp4_shard": _ffn_fwd_tp4_shard,
    "ffn_grad_n2048": _ffn_grad,
    "decode_dense_t4096": _decode_dense,
    "paged_decode_f32": _paged_f32,
    "paged_decode_q8": _paged_q8,
    "quantize_int8": _quantize,
    "dequantize_int8": _dequantize,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(shape, case):
    fn, args = CASES[case](shape)
    _compile(fn, *args)



# One HLO instruction: ``%name = dtype[dims]{layout} opcode(``.
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* (\S+?)\(")


def test_dense_decode_step_updates_kv_cache_in_place(shape, monkeypatch):
    """The whole qwen3-4b decode step as the engine compiles it (caches
    donated, B=4, T=4096, bf16, Pallas attention and FFN): each layer's
    new K/V row is scattered into the stacked cache the layer scan
    carries.  No whole-cache copy, no per-layer write-back and no
    scatter into a per-layer copy may appear, and the step's scratch
    memory stays far below one K leaf (a second copy of the cache would
    not)."""
    monkeypatch.setenv("REPRO_FFN_IMPL", "pallas")
    cfg = get_config("qwen3-4b")
    B, T = 4, 4096
    L = cfg.groups[0].repeats

    def abstract(tree):
        return jax.tree.map(lambda a: shape(a.shape, a.dtype), tree)

    params = abstract(abstract_params(model_specs(cfg), BF16))
    caches = abstract(kvcache.abstract_cache(cfg, B, T))
    step = make_decode_step(cfg, make_plan(cfg, {}, shape_kind="decode",
                                           seq_len=T), None,
                            attn_impl="pallas", advance_pos=True)
    was = ops._INTERPRET
    ops.set_interpret_mode(False)
    try:
        compiled = jax.jit(step, donate_argnums=(2,)).lower(
            params, shape((B, 1), I32), caches, shape((B,), I32)).compile()
    finally:
        ops.set_interpret_mode(was)
    text = compiled.as_text()
    assert "tpu_custom_call" in text

    k_leaf = caches[0]["sub0"]["k"]
    assert k_leaf.shape == (L, B, T, KV, DH)
    k_bytes = k_leaf.size * jnp.dtype(k_leaf.dtype).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < k_bytes

    stacked = ",".join(map(str, (L, B, T, KV, DH)))
    per_layer = ",".join(map(str, (B, T, KV, DH)))
    moves, writes = [], []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        name, dims, op = m.groups()
        if dims not in (stacked, per_layer):
            continue
        if any(w in name or w == op for w in ("copy", "dynamic-update-slice")):
            moves.append(name)
        if op == "scatter":
            writes.append((name, dims))
    assert not moves, f"whole K/V cache or layer slice moved: {moves}"
    assert writes and all(d == stacked for _, d in writes), writes
