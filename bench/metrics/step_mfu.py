"""Model FLOP/s utilisation of the whole serving step over the traced
window: the operations the useful tokens need (each admitted prompt's
prefill at its true length, each decode token at its live context; no
padding rows or columns, no empty slot) over window x chips x peak bf16
FLOP/s."""


def read(ctx):
    w, cost, peak = ctx["window"], ctx["cost"], ctx["peak"]
    if peak is None:
        return None
    flops = sum(cost.prefill_flops(len(rc.req.prompt)) for rc in w.recs
                if rc.req.admitted_at and w.t0 <= rc.req.admitted_at < w.t_end)
    flops += sum(cost.decode_flops(c) for tick in w.tick_contexts
                 for c in tick)
    return 100.0 * flops / (ctx["window_s"] * ctx["chips"]
                            * peak["flops_bf16"])
