"""Training launcher: preflight -> restore -> step loop -> checkpoints.

    PYTHONPATH=src python -m repro.launch.train --arch exanode-100m \
        --steps 200 --batch 8 --seq 128 [--smoke] [--mesh 2x4] \
        [--grad-sync hierarchical] [--ckpt-dir /tmp/ckpt]

On this CPU container use --smoke (reduced config) and a small mesh; the
same driver runs the production mesh on real hardware (the dry-run proves
those configs compile).  The loop wires together every subsystem through
one ``repro.runtime.Runtime``: data/pipeline (deterministic, resumable),
the Runtime's compiled train step (tier-aware sync), checkpoint/manager
(async, rotated), ft/straggler (step-time watchdog), launch/preflight (the
paper's bring-up sequence).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.data.pipeline import DataConfig, synthetic_batch
from repro.ft.straggler import StragglerMonitor
from repro.launch import preflight as pf
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import mesh_from_spec
from repro.optim.adamw import AdamWConfig
from repro.optim.schedules import make_schedule
from repro.runtime import Runtime
from repro.checkpoint.manager import CheckpointManager


def train_loop(cfg, mesh, *, steps: int, global_batch: int, seq_len: int,
               grad_sync: str = "hierarchical", microbatches: int = 1,
               lr: float = 3e-4, ckpt_dir: str = "", save_every: int = 50,
               run_preflight: bool = True, log_every: int = 10,
               param_dtype=jnp.float32):
    rt = Runtime.create(cfg, mesh, shape_kind="train", seq_len=seq_len,
                        grad_sync=grad_sync, param_dtype=param_dtype)
    print(rt.describe(), flush=True)

    schedule = make_schedule("cosine", peak=lr, warmup=min(100, steps // 10),
                             total=steps)
    jstep = rt.compile_train_step(schedule=schedule, opt_cfg=AdamWConfig(),
                                  microbatches=microbatches)
    shardings = rt.state_shardings

    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq_len,
                      global_batch=global_batch,
                      frontend_len=cfg.frontend_len if cfg.frontend else 0,
                      d_model=cfg.d_model)
    bspec = rt.batch_sharding

    def put(batch):
        return {k: jax.device_put(v, bspec) for k, v in batch.items()}

    mgr = CheckpointManager(ckpt_dir, save_every=save_every) if ckpt_dir \
        else None

    with mesh:
        if run_preflight:
            rep = pf.run_preflight(mesh)
            print(rep.summary(), flush=True)
            if not rep.ok:
                raise SystemExit("preflight failed; not starting")

        state = jax.device_put(rt.init_train_state(), shardings)
        start = 0
        if mgr is not None:
            restored, at = mgr.restore_latest(state, shardings=shardings)
            if restored is not None:
                state, start = restored, at + 1
                print(f"restored checkpoint @ step {at}", flush=True)

        mon = StragglerMonitor()
        t_begin = time.time()
        for step in range(start, steps):
            batch = put(synthetic_batch(dcfg, step))
            mon.step_start()
            state, metrics = jstep(state, batch)
            jax.block_until_ready(metrics["loss"])
            rep = mon.step_end(step)
            if rep.action != "ok":
                print(f"[straggler] step {step}: {rep.step_time:.3f}s "
                      f"({rep.ratio:.1f}x median) -> {rep.action}", flush=True)
            if mgr is not None:
                mgr.maybe_save(step, state)
            if step % log_every == 0 or step == steps - 1:
                print(f"step {step:5d} loss={float(metrics['loss']):.4f} "
                      f"lr={float(metrics['lr']):.2e} "
                      f"gnorm={float(metrics['grad_norm']):.3f}", flush=True)
        if mgr is not None:
            mgr.maybe_save(steps - 1, state, force=True)
            mgr.wait()
        dt = time.time() - t_begin
        tok = global_batch * seq_len * (steps - start)
        print(f"done: {steps - start} steps, {tok} tokens, "
              f"{tok / max(dt, 1e-9):.0f} tok/s (host wall)", flush=True)
    return state


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="exanode-100m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="", help="e.g. 2x4 or 2x2x2")
    ap.add_argument("--grad-sync", default="hierarchical",
                    choices=["flat", "hierarchical", "hierarchical_int8"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--no-preflight", action="store_true")
    ap.add_argument("--bf16-params", action="store_true")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = mesh_from_spec(args.mesh or f"1x{len(jax.devices())}")
    train_loop(cfg, mesh, steps=args.steps, global_batch=args.batch,
               seq_len=args.seq, grad_sync=args.grad_sync,
               microbatches=args.microbatches, lr=args.lr,
               ckpt_dir=args.ckpt_dir, save_every=args.save_every,
               run_preflight=not args.no_preflight,
               param_dtype=jnp.bfloat16 if args.bf16_params
               else jnp.float32)


if __name__ == "__main__":
    main()
