"""Time a request waits in the engine's queue before its prefill starts,
in s: the 90th percentile of the program's ``req:queued`` spans
(submission to admission, one per admitted request) that end inside the
window."""
from harness import stats


def read(ctx):
    w = ctx["window"]
    waits = [e - s for name, s, e, _ in ctx["host_spans"]
             if name == "req:queued" and e is not None
             and w.t0 <= e <= w.t_end]
    return stats.percentile(waits, 90) if waits else None
