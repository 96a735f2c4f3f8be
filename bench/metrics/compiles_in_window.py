"""XLA programs compiled (or loaded from the compilation cache) inside
the window: the program's ``compile`` instants, which its compile
listener marks on the Tracer, between the window's opening and its
close.  Every shape is warmed before the window, so this should read 0.

A program without the listener (``repro.obs.watching_compiles`` missing
or false) marks no compiles, and reads nothing rather than a false 0."""


def read(ctx):
    try:
        from repro.obs import watching_compiles
    except ImportError:
        return None
    if not watching_compiles():
        return None
    w = ctx["window"]
    return float(sum(1 for name, s, e, _ in ctx["host_spans"]
                     if name == "compile" and e is None
                     and w.t0 <= s <= w.t_end))
