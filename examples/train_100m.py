"""End-to-end driver: train the ~100M exanode demo config.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python examples/train_100m.py [--steps 300]

Full production path — preflight (the paper's bring-up sequence), an
8-device (2,2,2) pod×data×model mesh, hierarchical grad sync, async
checkpoints, straggler watch — on the real 100M-parameter config.  Loss
on the synthetic bigram corpus drops well below the uniform floor
(ln 32000 ≈ 10.4) within a few hundred steps.

NOTE: on this CPU container the full 100M model at seq 512 takes a few
seconds/step; pass --steps 40 for a quick check, the default 300 for the
brief's "few hundred steps".
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                                               # noqa: E402

from repro.configs import get_config                      # noqa: E402
from repro.launch.mesh import mesh_from_spec              # noqa: E402
from repro.launch.train import train_loop                 # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/exanode_100m_ckpt")
    ap.add_argument("--distributed", action="store_true",
                    help="8-device (2,2,2) mesh with int8 cross-pod sync; "
                         "~8x slower on this 1-core container (each fake "
                         "device is a serialized partition)")
    args = ap.parse_args()

    cfg = get_config("exanode-100m")
    n = len(jax.devices())
    if args.distributed and n >= 8:
        mesh = mesh_from_spec("2x2x2")
        sync = "hierarchical_int8"
    else:
        mesh = mesh_from_spec("1x1")
        sync = "hierarchical"
    train_loop(cfg, mesh, steps=args.steps, global_batch=args.batch,
               seq_len=args.seq, grad_sync=sync,
               ckpt_dir=args.ckpt_dir, save_every=100, lr=3e-4,
               log_every=20)


if __name__ == "__main__":
    main()
