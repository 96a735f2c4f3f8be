"""Time the host waits for an admitted group's first tokens, in ms: the
mean of the program's ``admit:wait`` spans (the blocking read after the
group's prefill and splice are dispatched) that start inside the window.
The next decode step is dispatched only after it."""


def read(ctx):
    w = ctx["window"]
    waits = [e - s for name, s, e, _ in ctx["host_spans"]
             if name == "admit:wait" and e is not None
             and w.t0 <= s <= w.t_end]
    return 1e3 * sum(waits) / len(waits) if waits else None
