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


FAULTS = {"state_unchanged": state_unchanged,
          "half_the_slots": half_the_slots, "token_altered": token_altered}
