"""``run.py`` refuses anything but the chips its cell asks for, and a
checkout that holds only the benchmark."""
import json
import os
import shutil
import subprocess
import sys

import tiny

ROOT = tiny.BENCH.parent


def _run(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-4b.decode-long",
         "--seed", "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    for line in out.strip().splitlines()[-1:]:
        try:
            return "correct" not in json.loads(line)
        except ValueError:
            return True
    return True


def test_run_refuses_a_cpu_platform():
    p = _run(ROOT)
    assert p.returncode != 0 and _no_result(p.stdout)
    assert "TPU" in p.stderr


def test_run_refuses_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and _no_result(p.stdout)
