"""Roofline share of the dense flash-decode kernel
(``kernels/decode_attention.py``; instructions named ``decode_attention``
on the trace's "XLA Ops" line), in %.

Operations and bytes are those of the live context only: for each slot
that serves a request, the cache entries at positions up to its own, not
the whole capacity the kernel walks today, so a kernel that stops at the
live context is credited and the share cannot pass 100%.  The calls of
each chip are matched in order to the decode ticks the benchmark
dispatched (one call per layer per tick)."""
from harness import cost, hlo
from harness import trace as tr

KERNEL = "decode_attention"


def read(ctx):
    peak, ticks = ctx["peak"], ctx["window"].tick_contexts
    if peak is None or not ticks:
        return None
    least = took = 0.0
    for chip in ctx["trace"]["chips"].values():
        calls = sorted(tr.kernel_events(chip, KERNEL, float("-inf"),
                                        float("inf")), key=lambda c: c[1])
        if not calls or len(calls) % len(ticks):
            return None
        per_tick = len(calls) // len(ticks)
        for i, (name, s, e) in enumerate(calls):
            op = hlo.parse(name)
            least += cost.decode_attention_live(
                op, ticks[i // per_tick]).min_seconds(peak)
            took += (e - s) / 1e9
    return 100.0 * least / took if took else None
