"""Host time per engine tick outside the wait for the device, in ms: each
``tick`` span of the program's Tracer (host clock) less its ``collect``
span, which blocks on the step's tokens (the few microseconds of token
bookkeeping after the read go out with it).  Ticks that admit a request
are left out, since their ``admit`` span waits for the prefill's first
token.  The mean over the remaining ticks of the window."""


def read(ctx):
    w = ctx["window"]
    spans = [s for s in ctx["host_spans"] if w.t0 <= s[1] <= w.t_end]
    ticks = [(s, e) for name, s, e, depth in spans
             if name == "tick" and depth == 0 and e is not None]
    admits = [s for name, s, e, _ in spans if name == "req:admit"]
    waits = [(s, e) for name, s, e, _ in spans
             if name == "collect" and e is not None]
    host = []
    for ts, te in ticks:
        if any(ts <= a <= te for a in admits):
            continue
        host.append((te - ts) - sum(e - s for s, e in waits
                                    if ts <= s and e <= te))
    return 1e3 * sum(host) / len(host) if host else None
