"""Benchmark entrypoint: one section per paper table/figure analog.

    PYTHONPATH=src python -m benchmarks.run

Emits ``name,us_per_call,derived`` CSV lines per bench.
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_collectives, bench_linktest, bench_memtest,
                            bench_roofline, bench_serve, bench_step)
    sections = [
        ("linktest (paper §III.b IBERT/PRBS-31)", bench_linktest.main),
        ("memtest (paper §III.b DDR soak)", bench_memtest.main),
        ("collectives (paper thesis: tiered vs flat)",
         bench_collectives.main),
        ("step timing (smoke-scale, CPU wall)", bench_step.main),
        ("serve engine (fast path vs legacy)", bench_serve.main),
        ("roofline (from dry-run records)", bench_roofline.main),
    ]
    failed = []
    for title, fn in sections:
        print(f"# === {title} ===", flush=True)
        try:
            fn()
        except Exception:  # noqa: BLE001 - report all sections
            traceback.print_exc()
            failed.append(title)
    if failed:
        print("# FAILED sections:", failed)
        sys.exit(1)
    print("# all benchmark sections completed")


if __name__ == "__main__":
    main()
