"""Fault-tolerance scenario: lose devices mid-run, re-mesh, resume.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python examples/elastic_failover.py

Phase 1 trains on a (4, 2) data×model mesh with checkpoints.  Then two
"hosts" die (we drop 4 of 8 devices).  Phase 2: ft/elastic picks the
largest surviving mesh with the same TP width (2, 2), doubles the
grad-accumulation factor so the global batch (and therefore the loss
trajectory) is preserved, restores the last checkpoint **into the new
shardings** (restore-time resharding), and continues — the loss curve
continues from where it left off.
"""
import os
import sys

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                                                # noqa: E402
import numpy as np                                        # noqa: E402

from repro.checkpoint.manager import CheckpointManager    # noqa: E402
from repro.configs import get_smoke_config                # noqa: E402
from repro.core.topology import make_plan                 # noqa: E402
from repro.data.pipeline import DataConfig, synthetic_batch  # noqa: E402
from repro.ft.elastic import make_elastic_mesh, plan_remesh  # noqa: E402
from repro.launch.mesh import mesh_from_spec              # noqa: E402
from repro.optim.schedules import make_schedule           # noqa: E402
from repro.runtime import Runtime                         # noqa: E402

CKPT = "/tmp/elastic_demo_ckpt"
GLOBAL_BATCH, SEQ = 16, 64


def run_phase(mesh, cfg, dcfg, *, steps, start, microbatches, restore):
    rt = Runtime.create(cfg, mesh, shape_kind="train", seq_len=SEQ,
                        grad_sync="hierarchical")
    shardings = rt.state_shardings
    jstep = rt.compile_train_step(
        microbatches=microbatches,
        schedule=make_schedule("constant", peak=3e-3), donate=False)
    mgr = CheckpointManager(CKPT, save_every=5, async_save=False)
    with mesh:
        if restore:
            state, at = mgr.restore_latest(rt.init_train_state(),
                                           shardings=shardings)
            assert state is not None
            print(f"  restored step {at} into mesh "
                  f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")
            start = at + 1
        else:
            state = jax.device_put(rt.init_train_state(), shardings)
        bspec = rt.batch_sharding
        losses = []
        for s in range(start, start + steps):
            batch = {k: jax.device_put(v, bspec)
                     for k, v in synthetic_batch(dcfg, s).items()}
            state, metrics = jstep(state, batch)
            mgr.maybe_save(s, state)
            losses.append(float(metrics["loss"]))
        mgr.maybe_save(start + steps - 1, state, force=True)
        mgr.wait()
    return losses, start + steps - 1


def main():
    import shutil
    shutil.rmtree(CKPT, ignore_errors=True)
    cfg = get_smoke_config("exanode-100m")
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=SEQ,
                      global_batch=GLOBAL_BATCH, branch=4)

    print("phase 1: healthy mesh (4 data x 2 model), 15 steps")
    mesh1 = mesh_from_spec("4x2")
    losses1, last = run_phase(mesh1, cfg, dcfg, steps=15, start=0,
                              microbatches=1, restore=False)
    print(f"  loss {losses1[0]:.3f} -> {losses1[-1]:.3f}")

    print("FAILURE: 4 of 8 devices lost (one 'MCM' down)")
    plan1 = make_plan(cfg, {"data": 4, "model": 2})
    decision = plan_remesh(cfg, old_plan=plan1, n_surviving=4,
                           global_batch=GLOBAL_BATCH, seq_len=SEQ,
                           old_microbatches=1)
    print(f"  remesh decision: shape={decision.mesh_shape} "
          f"microbatches={decision.microbatches} ({decision.note})")

    print("phase 2: resume on the surviving mesh")
    mesh2 = make_elastic_mesh(decision, devices=jax.devices()[:4])
    losses2, _ = run_phase(mesh2, cfg, dcfg,
                           steps=10, start=last + 1,
                           microbatches=decision.microbatches, restore=True)
    print(f"  loss {losses2[0]:.3f} -> {losses2[-1]:.3f}")

    # the resumed trajectory must continue, not restart
    assert losses2[0] < losses1[0], (losses1[0], losses2[0])
    print("elastic_failover OK")


if __name__ == "__main__":
    main()
