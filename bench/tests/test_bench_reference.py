"""The reference runs layer-outer (every sampled sequence through one
layer before the next) and gives, bit for bit, the gaps of the order it
replaced: each request through every layer alone."""
import numpy as np

import per_request
import tiny
from harness import check, serve

SEED = 2**32 + 41


class _Req:
    def __init__(self, prompt, generated):
        self.prompt, self.generated = prompt, generated


def requests(vocab: int, shapes) -> list:
    """Requests of the given (prompt, served) lengths, token ids drawn at
    random: each crosses a different number of padding blocks."""
    rng = np.random.default_rng(3)
    return [_Req(rng.integers(0, vocab, p, dtype=np.int32),
                 [int(t) for t in rng.integers(0, vocab, g)])
            for p, g in shapes]


def test_layer_outer_gaps_equal_the_per_request_order():
    c = tiny.cell()
    rt, _ = serve.build(c, SEED)
    reqs = requests(c.config["vocab_size"],
                    [(700, 300), (1500, 40), (30, 5), (1025, 1)])
    got = check.gaps(rt.params, c.config, reqs, control=True)
    per_request.assert_same(
        got, per_request.gaps(rt.params, c.config, reqs, control=True))
    assert check.gaps(rt.params, c.config, [], control=True) == {
        "program": [], "control": []}
