"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.  A kind that is not here is an error.

TPU v5e ("TPU v5 lite"): 197 TFLOP/s bf16 and 819 GB/s of HBM bandwidth
(Google Cloud documentation, "TPU v5e").  Vector-memory (VMEM) read
bandwidth is not in that document; it is the figure the TPU profiler
writes into the device plane of its trace (``peak_vmem_rd_bw_gigabytes_
per_second`` = 18432) and is used only for operands that the compiler
placed in VMEM (memory space 1).
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "vmem_bytes_per_s": 18432e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud docs 'TPU v5e'; VMEM read bw from the "
                  "profiler's device plane",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
