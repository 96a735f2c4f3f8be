"""``run.execute`` with the model tensor-parallel over four devices
(virtual CPU devices here, in a child process that asks for them): a
chat-shaped closed loop whose clients outnumber ``max_admit``, so that
admissions happen all through the window, with untied output weights."""
import json
import os
import subprocess
import sys

import tiny

RUN = """
import importlib.util, json, sys
sys.path[:0] = [{tests!r}]
import tiny
spec = importlib.util.spec_from_file_location("bench_run", tiny.BENCH / "run.py")
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)
c = tiny.cell(tp=4, tied=False)
c.config.update(num_attention_heads=8, num_key_value_heads=4)
c.traffic.update(
    clients=16, strata=64,
    prompt_len={{"dist": "lognormal", "median": 24, "sigma": 0.7,
                 "min": 8, "max": 48}},
    output_len={{"dist": "lognormal", "median": 8, "sigma": 0.7,
                 "min": 4, "max": 16}},
    engine={{"slots": 16, "capacity": 64, "kv_layout": "dense",
             "max_admit": 4}},
    check={{"min_tokens": 64}})
res, _ = run.execute(c, 2**33 + 7, 1.5, False, log=lambda *a, **k: None)
print(json.dumps(res))
"""


def test_tensor_parallel_cell_runs_through_execute():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run(
        [sys.executable, "-c", RUN.format(tests=str(tiny.BENCH / "tests"))],
        env=env, cwd=str(tiny.BENCH.parent), capture_output=True, text=True,
        timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4 and res["failed"] == 0
    assert res["attempted"] > 16        # admissions inside the window
    assert {"output_tokens_per_s", "itl_p99_s", "setup_s"} == set(
        res["metrics"])
