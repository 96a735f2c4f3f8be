"""``run.execute`` with the model tensor-parallel over four devices
(virtual CPU devices here, in a child process that asks for them): a
chat-shaped closed loop whose clients outnumber ``max_admit``, so that
admissions happen all through the window, with untied output weights;
and the layer-outer reference on those sharded weights."""
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


EXCHANGE = """
import importlib.util, json, sys
sys.path[:0] = [{tests!r}]
import tiny, faults
spec = importlib.util.spec_from_file_location("bench_run", tiny.BENCH / "run.py")
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)
c = tiny.cell(tp=4, tied=False)
c.config.update(num_attention_heads=8, num_key_value_heads=4)
c.traffic.update(output_len={{"dist": "uniform", "min": 24, "max": 48}},
                 engine={{"slots": 2, "capacity": 128, "kv_layout": "dense",
                         "max_admit": 2}}, check={{"min_tokens": 96}})
res, _ = run.execute(c, 2**33 + 11, 1.0, False, log=lambda *a, **k: None,
                     wrap_runtime=faults.exchange_left_out)
print(json.dumps(res))
"""

REFERENCE = """
import json, sys
sys.path[:0] = [{tests!r}]
import tiny, per_request, test_bench_reference as tr
from harness import check, serve
c = tiny.cell(tp=4, tied=False)
c.config.update(num_attention_heads=8, num_key_value_heads=4)
rt, _ = serve.build(c, 2**33 + 9)
reqs = tr.requests(c.config["vocab_size"], [(700, 300), (1500, 40), (30, 5)])
got = check.gaps(rt.params, c.config, reqs, control=True)
per_request.assert_same(
    got, per_request.gaps(rt.params, c.config, reqs, control=True))
print(json.dumps({{"sharded": len(rt.params["embed"].sharding.device_set),
                  "requests": len(got["program"])}}))
"""


def _child(script: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run(
        [sys.executable, "-c", script.format(tests=str(tiny.BENCH / "tests"))],
        env=env, cwd=str(tiny.BENCH.parent), capture_output=True, text=True,
        timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_tensor_parallel_cell_runs_through_execute():
    res = _child(RUN)
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4 and res["failed"] == 0
    assert res["attempted"] > 16        # admissions inside the window
    assert {"output_tokens_per_s", "itl_p99_s", "setup_s"} == set(
        res["metrics"])


def test_layer_outer_reference_equals_per_request_order_on_a_mesh():
    """Weights sharded over four devices, gathered to one per layer."""
    assert _child(REFERENCE) == {"sharded": 4, "requests": 3}


def test_exchange_left_out_reads_not_correct():
    """The decode step's partial sums not summed over the four devices."""
    res = _child(EXCHANGE)
    assert res["device"]["count"] == 4
    assert not res["correct"]
    assert res["checks"]["mean_logit_gap"]["value"] > 2 * tiny.LIMIT
