"""A run with the timed path broken underneath reads ``correct`` false.

Each test drives the rest of a run (build, warm-up, the window, the
comparison) on the CPU at toy widths, skipping only the look for a chip."""
import importlib.util

import pytest

import faults
import tiny

_spec = importlib.util.spec_from_file_location("bench_run",
                                               tiny.BENCH / "run.py")
run = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(run)

SEED = 2**33 + 17


def _cell():
    c = tiny.cell()
    c.traffic["output_len"] = {"dist": "uniform", "min": 24, "max": 48}
    c.traffic["engine"]["capacity"] = 128
    c.traffic["check"]["min_tokens"] = 96
    return c


def _quiet(*a, **k):
    pass


def test_sound_run_is_correct():
    res, _ = run.execute(_cell(), SEED, 1.0, False, log=_quiet)
    assert res["correct"] and res["failed"] == 0
    assert res["checks"]["mean_logit_gap"]["value"] < tiny.LIMIT / 5
    assert list(res)[-1] == "checks"
    assert res["metrics"]["output_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_reads_not_correct(fault):
    res, _ = run.execute(_cell(), SEED, 1.0, False, log=_quiet,
                         wrap_runtime=faults.FAULTS[fault])
    assert not res["correct"]
    assert res["checks"]["mean_logit_gap"]["value"] > 2 * tiny.LIMIT


def test_open_loop_run():
    """A mix with scheduled arrivals: requests are due on the schedule, and
    the window serves them."""
    c = _cell()
    c.traffic.update(loop="open", rate=40.0, burst_cv=2.0)
    res, _ = run.execute(c, SEED, 1.0, False, log=_quiet)
    assert res["correct"] and 10 < res["attempted"] < 200
    assert res["metrics"]["output_tokens_per_s"]["value"] > 0
