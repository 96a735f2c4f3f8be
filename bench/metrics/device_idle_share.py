"""Share of the traced window in which no instruction ran on the chip:
1 - (union of the "XLA Ops" intervals) / window, per chip; the highest
chip, in %."""
from harness import trace as tr


def read(ctx):
    t, lo, hi = ctx["trace"], ctx["lo"], ctx["hi"]
    if not t["chips"]:
        return None
    return 100.0 * max(1.0 - tr.busy_ns(c, lo, hi) / (hi - lo)
                       for c in t["chips"].values())
