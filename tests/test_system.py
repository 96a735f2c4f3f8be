"""End-to-end system tests: the train/serve launchers and the multi-device
distribution paths, run in subprocesses (the 8-device XLA host-platform
override must not leak into this process — smoke tests see 1 device)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV8 = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
            XLA_FLAGS="--xla_force_host_platform_device_count=8")
ENV1 = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))


def _run(code: str, env, timeout=600):
    return subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


def test_train_launcher_end_to_end(tmp_path):
    """preflight -> train -> checkpoint -> restore, on an 8-device mesh."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "exanode-100m",
         "--smoke", "--steps", "12", "--batch", "8", "--seq", "32",
         "--mesh", "2x2x2", "--ckpt-dir", str(tmp_path), "--save-every", "5"],
        env=ENV8, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "preflight: PASS" in r.stdout
    assert "done: 12 steps" in r.stdout
    # restart restores
    r2 = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "exanode-100m",
         "--smoke", "--steps", "14", "--batch", "8", "--seq", "32",
         "--mesh", "2x2x2", "--ckpt-dir", str(tmp_path), "--no-preflight"],
        env=ENV8, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r2.returncode == 0, r2.stdout[-2000:] + r2.stderr[-2000:]
    assert "restored checkpoint @ step" in r2.stdout


def test_serve_launcher_end_to_end():
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "exanode-100m",
         "--smoke", "--requests", "4", "--max-new", "4", "--slots", "2",
         "--capacity", "32", "--no-preflight"],
        env=ENV1, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "finished=4" in r.stdout


CACHE_CODE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile!r}:
    jax.jit(lambda x: jnp.sin(x) * 2 + 1)(jnp.ones(64)).block_until_ready()
"""


def test_compile_cache_goes_where_the_env_says(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the cache is written there and
    nothing else is configured."""
    cache = tmp_path / "cache"
    env = dict(ENV1, JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    r = _run(CACHE_CODE.format(compile=True), env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [str(cache), str(cache)]
    assert any(cache.iterdir())


def test_compile_cache_defaults_to_fixed_checkout_path():
    """Unset: a fixed directory at the root of the checkout — no temp dir,
    pid or time in it, so the next run finds the same cache."""
    env = {k: v for k, v in ENV1.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    r = _run(CACHE_CODE.format(compile=False), env)
    assert r.returncode == 0, r.stderr[-2000:]
    want = os.path.join(REPO, ".jax_cache")
    assert r.stdout.split() == [want, want]


GRAD_SYNC_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
from repro.configs import get_smoke_config
from repro.core.topology import make_plan, batch_pspec
from repro.launch.mesh import mesh_from_spec
from repro.models.registry import model_specs
from repro.train.state import init_train_state, train_state_shardings
from repro.train.steps import make_train_step

mesh = mesh_from_spec("2x2x2")
cfg = get_smoke_config("{arch}")
specs = model_specs(cfg)
results = {{}}
for sync in ["flat", "hierarchical", "hierarchical_int8"]:
    plan = make_plan(cfg, {{"pod": 2, "data": 2, "model": 2}}, grad_sync=sync)
    step = make_train_step(cfg, plan, specs, mesh)
    with mesh:
        state = jax.device_put(init_train_state(specs, jax.random.PRNGKey(0), plan),
                               train_state_shardings(specs, plan, mesh))
        toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 16)).astype(np.int32)
        bspec = NamedSharding(mesh, batch_pspec(plan))
        batch = {{"tokens": jax.device_put(toks, bspec), "labels": jax.device_put(toks, bspec)}}
        sh = train_state_shardings(specs, plan, mesh)
        jstep = jax.jit(step, in_shardings=(sh, None), out_shardings=(sh, None))
        for i in range(3):
            state, metrics = jstep(state, batch)
        results[sync] = float(metrics["loss"])
        assert jnp.isfinite(metrics["loss"])
# all three syncs compute the same math (int8 is lossy but EF-bounded)
vals = list(results.values())
assert abs(vals[0] - vals[1]) < 0.15, results
assert abs(vals[0] - vals[2]) < 0.3, results
print("GRADSYNC_OK", results)
"""


@pytest.mark.parametrize("arch", ["exanode-100m", "mixtral-8x7b"])
def test_three_grad_sync_modes_on_pod_mesh(arch):
    r = _run(GRAD_SYNC_CODE.format(arch=arch), ENV8, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + "\n" + r.stderr[-3000:]
    assert "GRADSYNC_OK" in r.stdout


DRYRUN_SMOKE = """
import sys
from repro.launch import dryrun
dryrun.main(["--arch", "xlstm-125m", "--shape", "decode_32k", "--no-analyze"])
print("DRYRUN_OK")
"""


def test_dryrun_one_cell_production_mesh():
    """One real dry-run cell (256-device mesh) end to end."""
    r = _run(DRYRUN_SMOKE, ENV1, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + "\n" + r.stderr[-3000:]
    assert "DRYRUN_OK" in r.stdout


def test_dryrun_skips_inapplicable_cells():
    code = """
from repro.launch import dryrun
rec = dryrun.run_cell("gemma-2b", "long_500k", verbose=False)
assert rec["status"] == "SKIP", rec
print("SKIP_OK")
"""
    r = _run(code, ENV1, timeout=300)
    assert r.returncode == 0, r.stdout[-1000:] + r.stderr[-2000:]
    assert "SKIP_OK" in r.stdout
