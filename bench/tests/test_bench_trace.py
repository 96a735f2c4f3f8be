"""The trace reduction on a trace recorded on a TPU v5e: three decode ticks
of qwen3-4b (4 slots, capacity 4096) through the dense engine."""
from pathlib import Path

import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
from harness import cost, hlo
from harness import trace as tr
from harness.peaks import peaks

DATA = Path(__file__).parent / "data" / "decode3.trace.json.gz"
PEAK = peaks("TPU v5 lite")


@pytest.fixture(scope="module")
def recorded():
    t = tr.read_saved(str(DATA))
    chip = t["chips"][0]
    lo = min(s for _, s, _ in chip["modules"])
    hi = max(e for _, _, e in chip["modules"])
    return t, chip, lo, hi


def test_modules_and_kernels_are_found_by_name(recorded):
    t, chip, lo, hi = recorded
    assert [hlo.base_name(n) for n, _, _ in chip["modules"]].count(
        "jit_decode") == 3
    assert len(tr.kernel_events(chip, "decode_attention", lo, hi)) == 3 * 36
    assert len(tr.kernel_events(chip, "swiglu_ffn", lo, hi)) == 3 * 36


def test_busy_time_is_the_union_within_the_window(recorded):
    t, chip, lo, hi = recorded
    busy = tr.busy_ns(chip, lo, hi)
    assert 0 < busy <= hi - lo
    # the three ticks run back to back: the device is busy nearly always
    assert busy / (hi - lo) > 0.9
    assert tr.busy_ns(chip, lo, lo + (hi - lo) / 2) < busy


def test_union_merges_overlaps_and_nesting():
    assert tr.union([(0, 10), (2, 3), (5, 12), (20, 21)]) == [[0, 12],
                                                              [20, 21]]


def test_decode_attention_operands_parse(recorded):
    _, chip, lo, hi = recorded
    name = tr.kernel_events(chip, "decode_attention", lo, hi)[0][0]
    op = hlo.parse(name)
    assert op.name == "decode_attention" and op.opcode == "custom-call"
    pos, q, k, v, kv_pos = op.operands
    assert q.dims == (4, 8, 4, 128) and k.dims == (4, 4096, 8, 128)
    assert pos.dtype == "s32" and kv_pos.dims[-1] == 4096
    assert k.vmem and v.vmem        # the step stages K/V tiles in VMEM


def test_kernel_roofline_shares_stay_under_100(recorded):
    _, chip, lo, hi = recorded
    for name, s, e in tr.kernel_events(chip, "swiglu_ffn", lo, hi):
        least = cost.fused_ffn(hlo.parse(name)).min_seconds(PEAK)
        assert 0 < least <= (e - s) / 1e9
    for name, s, e in tr.kernel_events(chip, "decode_attention", lo, hi):
        work = cost.decode_attention_live(hlo.parse(name), [4096] * 4)
        assert 0 < work.min_seconds(PEAK) <= (e - s) / 1e9


def test_top_ops_leave_out_loops(recorded):
    t, _, lo, hi = recorded
    top = tr.top_ops(t, lo, hi)
    names = [n for n, _ in top]
    assert len(top) == 10 and not any(n.startswith("while") for n in names)
    assert "decode_attention" in names
    assert all(a[1] >= b[1] for a, b in zip(top, top[1:]))


def test_idle_gaps_are_named_by_host_spans(recorded):
    t, chip, lo, hi = recorded
    t_sync = 100.0
    off = t["sync_ns"] - t_sync * 1e9
    spans = [("collect", (lo - off) / 1e9, (hi - off) / 1e9)]
    gaps = tr.idle_gaps(t, lo, hi, spans, t_sync, n=3)
    assert len(gaps) == 3 and all(g[0] == "collect" for g in gaps)
    assert all(0 <= g[1] <= (hi - lo) / 1e9 for g in gaps)
