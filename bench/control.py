"""The readings a cell's correctness limit is set from, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n>...

For each seed, in this one process, one run of the cell exactly as
``run.py`` makes it (``run.execute``: the timed path serves the traffic for
``--seconds``, then the sample is compared with the float32 reference),
with the control read on the same sample as well: the int8-weight,
bfloat16-matmul reference put in the program's place, reading the gap of
the token it puts first at each of the same positions.  Both are judged
at the limits in ``bench/limits/<cell>.json``.  One JSON line per seed.

The limit lies above the largest program reading and below the smallest
control reading.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

from harness.spec import load_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"{args.workload} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 3
    import run
    run.enable_compile_cache()
    for s in args.seeds:
        res, _ = run.execute(cell, s, args.seconds, False, control=True)
        print(json.dumps({"seed": s, "correct": res["correct"],
                          "checks": res["checks"],
                          "control": res["control"],
                          "metrics": res["metrics"]}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
