"""Faults planted under the timed path, for the tests that see ``correct``
come out false.  Each wraps the Runtime's step factories before the
engine is built from them."""
from __future__ import annotations

import jax.numpy as jnp


def _wrap_decode(rt, change):
    make = rt.make_decode_step

    def make_faulty(**kw):
        step = make(**kw)

        def faulty(params, token, caches, pos):
            return change(step, params, token, caches, pos)
        return faulty
    rt.make_decode_step = make_faulty


def state_unchanged(rt):
    """The decode step hands back the caches it was given."""
    def change(step, params, token, caches, pos):
        nxt, _, new_pos = step(params, token, caches, pos)
        return nxt, caches, new_pos
    _wrap_decode(rt, change)


def half_the_slots(rt):
    """Half of the slots are left out: the second half get the first
    half's tokens."""
    def change(step, params, token, caches, pos):
        nxt, caches, new_pos = step(params, token, caches, pos)
        h = nxt.shape[0] // 2
        return jnp.concatenate([nxt[:nxt.shape[0] - h], nxt[:h]]), caches, \
            new_pos
    _wrap_decode(rt, change)


def token_altered(rt):
    """Every decoded token is changed where it is produced."""
    vocab = rt.cfg.vocab_size

    def change(step, params, token, caches, pos):
        nxt, caches, new_pos = step(params, token, caches, pos)
        return (nxt + 1) % vocab, caches, new_pos
    _wrap_decode(rt, change)


def exchange_left_out(rt):
    """On a tensor-parallel mesh, the decode step's exchange between chips
    is left out: the row-parallel partial sums of each layer's output
    projections (attention ``wo`` over heads, FFN ``wo`` over columns) are
    not summed, and chip 0's partial goes on.  Planted by giving the step
    those weights with the rows the other chips hold set to zero."""
    tp = rt.mesh.shape["model"]

    def local(w, axis):
        keep = jnp.arange(w.shape[axis]) < w.shape[axis] // tp
        shape = [1] * w.ndim
        shape[axis] = w.shape[axis]
        return w * keep.reshape(shape).astype(w.dtype)

    def change(step, params, token, caches, pos):
        g = params["groups"][0]["sub0"]
        sub = dict(g, attn=dict(g["attn"], wo=local(g["attn"]["wo"], 1)),
                   ffn=dict(g["ffn"], wo=local(g["ffn"]["wo"], 1)))
        cut = dict(params, groups=[dict(params["groups"][0], sub0=sub)]
                   + list(params["groups"][1:]))
        return step(cut, token, caches, pos)
    _wrap_decode(rt, change)


# single-device faults; ``exchange_left_out`` needs a mesh
FAULTS = {"state_unchanged": state_unchanged,
          "half_the_slots": half_the_slots, "token_altered": token_altered}
