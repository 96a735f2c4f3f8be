"""Paper Table/Fig analog: IBERT PRBS link validation (paper §III.b).

The paper's result: all intra-board links between the 4 FPGAs stable at
10 Gbps under PRBS-31.  Ours: every mesh axis transports PRBS-31 payloads
bit-exactly through all-gather / ppermute / psum / all-to-all, with an
effective-bandwidth probe (host-timed; meaningful on real links).
"""
from __future__ import annotations

import jax

from benchmarks.common import emit
from repro.core import linktest
from repro.launch.mesh import mesh_from_spec


def main():
    n = len(jax.devices())
    mesh = mesh_from_spec(str(n))
    for payload in (1 << 12, 1 << 16, 1 << 20):
        reports = linktest.run_link_test(mesh, payload_bytes=payload)
        for r in reports:
            status = "ok" if r.ok else "FAIL"
            emit(f"linktest_prbs31_{r.axis}_{payload}B",
                 r.elapsed_s * 1e6,
                 f"bit_errors={r.bit_errors};status={status};"
                 f"eff_bw={r.eff_bandwidth / 1e9:.2f}GB/s")


if __name__ == "__main__":
    main()
