"""The correctness check's gaps in the order the reference ran before it
went layer-outer: each request through every layer alone, with each
layer's weights, the final norm and the output table brought to the
reference device for that request.  The tests hold ``check.gaps`` to it
bit for bit."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
from harness import check
from harness.reference import PAD, Reference, _head, gather, int8_round
from harness.spec import load_model


def _hidden(ref: Reference, tokens, control: bool):
    S = len(tokens)
    ids = np.zeros(-(-S // PAD) * PAD, np.int32)
    ids[:S] = tokens
    table = gather(ref.params["embed"], ref.dev)
    if control:
        table = int8_round(table.astype(jnp.float32), (0,))
    x = jnp.take(table, ref._put(jnp.asarray(ids)), axis=0).astype(
        jnp.float32)
    f = ref.model.layer(ref.config, control)
    for l in range(ref.layers):
        x = f(x, gather(ref.model.layer_weights(ref.params, l), ref.dev))
    return x


def _logits(ref: Reference, x, rows, control: bool):
    """The logits of ``rows``, with the table brought over for this
    request."""
    norm, out = ref.head()
    return _head(x[ref._put(jnp.asarray(rows))], norm, out,
                 vocab=ref.vocab, eps=ref.eps, control=control)


def gaps(params, config: dict, requests: list, control: bool) -> dict:
    ref = Reference(params, config, load_model(config))
    out = {"program": [], "control": []}
    for r in requests:
        served = np.asarray(r.generated, np.int32)
        seq, rows = check._positions(r.prompt, served)
        want = np.asarray(_logits(ref, _hidden(ref, seq, False), rows,
                                  False))
        best = want.max(-1)
        at = np.arange(len(rows))
        out["program"].append(best - want[at, served])
        if control:
            pick = np.asarray(_logits(ref, _hidden(ref, seq, True), rows,
                                      True)).argmax(-1)
            out["control"].append(best - want[at, pick])
    return out


def assert_same(a: dict, b: dict) -> None:
    """Every gap of every request equal, bit for bit."""
    for side in a:
        assert len(a[side]) == len(b[side]), side
        for x, y in zip(a[side], b[side]):
            np.testing.assert_array_equal(x, y)
