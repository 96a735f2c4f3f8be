"""Roofline share of the fused SwiGLU kernel (``kernels/fused_ffn.py``;
instructions named ``swiglu_ffn`` on the trace's "XLA Ops" line), over
every call in the trace (prefill and decode), in %.

Per call, from the operand shapes in the instruction: 6 N D F operations;
x, the three weight tiles and y once each, each counted against the
bandwidth of the memory it sits in.  The share is the sum of the calls'
least times over the sum of their measured times."""
from harness import cost, hlo
from harness import trace as tr

KERNEL = "swiglu_ffn"


def read(ctx):
    peak = ctx["peak"]
    if peak is None:
        return None
    least = took = 0.0
    for chip in ctx["trace"]["chips"].values():
        for name, s, e in tr.kernel_events(chip, KERNEL, float("-inf"),
                                           float("inf")):
            least += cost.fused_ffn(hlo.parse(name)).min_seconds(peak)
            took += (e - s) / 1e9
    return 100.0 * least / took if took else None
