"""Operations and bytes of each call, computed from its shapes.

A kernel's roofline share is the least time the chip could take for the
call, max(flops / peak flops, HBM bytes / HBM bandwidth, VMEM bytes / VMEM
bandwidth), over the time the call took.  Bytes are split by where the
compiler placed each operand (``hlo.Buffer.vmem``).

The model-level counts are each model type's own (``cost`` in
``bench/models/<model_type>.py``): the operations that the useful tokens
need, with no padding rows, no padded prompt columns, no slot that holds
no request.
"""
from __future__ import annotations

from dataclasses import dataclass

from harness.hlo import Op


@dataclass(frozen=True)
class Work:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    vmem_bytes: float = 0.0

    def min_seconds(self, peak: dict) -> float:
        return max(self.flops / peak["flops_bf16"],
                   self.hbm_bytes / peak["hbm_bytes_per_s"],
                   self.vmem_bytes / peak["vmem_bytes_per_s"])


def _split(buffers_and_bytes) -> Work:
    hbm = vmem = 0.0
    for buf, nbytes in buffers_and_bytes:
        if buf.vmem:
            vmem += nbytes
        else:
            hbm += nbytes
    return Work(0.0, hbm, vmem)


def fused_ffn(op: Op) -> Work:
    """SwiGLU y = (silu(x Wg) * (x Wu)) Wd over x [N, D], Wg/Wu [D, F],
    Wd [F, D]: 6 N D F operations; each operand read once, y written once."""
    x, wg, wu, wd = op.operands[:4]
    (y,) = op.outputs[:1]
    N, D = x.dims
    F = wg.dims[1]
    w = _split([(b, b.nbytes) for b in (x, wg, wu, wd, y)])
    return Work(6.0 * N * D * F, w.hbm_bytes, w.vmem_bytes)


def decode_attention_live(op: Op, contexts) -> Work:
    """One flash-decode call over q [B, KV, G, D] and caches [B, T, KV, D],
    counting only the live context: ``contexts`` holds, for each slot that
    serves a request, the number of cache entries it attends to (the entries
    at positions up to its own).  Per live entry: K and V rows of KV heads
    and one position word are read, and 4 G D operations per kv head are
    done (q.k and p.v)."""
    pos, q, k, v, kv_pos = op.operands[:5]
    _, KV, G, D = q.dims
    (out,) = op.outputs[:1]
    live = float(sum(contexts))
    kv_item = k.nbytes / max(1, k.dims[0] * k.dims[1] * KV * D)
    rows = len(contexts)
    per_row_q = q.nbytes / q.dims[0]
    per_row_o = out.nbytes / out.dims[0]
    w = _split([(k, live * KV * D * kv_item), (v, live * KV * D * kv_item),
                (kv_pos, live * 4), (q, rows * per_row_q),
                (out, rows * per_row_o)])
    return Work(4.0 * KV * G * D * live, w.hbm_bytes, w.vmem_bytes)
