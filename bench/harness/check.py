"""The comparison that decides ``correct`` for a served model.

After the window has closed, a sample of the requests drawn from the seed
(the one with the most served tokens and every one that held a slot at
the window's close always in it, then others until the sample holds
``min_tokens`` served tokens) is run through the reference once, over
each prompt followed by its served tokens: layer by layer, all the
sample's sequences through one layer before the next.  At the position
that produced each served token, the gap is how far the served token's
logit lies below the reference's best logit there.  Greedy decoding with
no error gives 0; a near-tie that rounding flipped gives a small gap.
The widest gap, the mean gap and the share of positions with a gap are
read over the sample; the cell's limits file names those compared.

The control reads the same positions with the int8 reference in the
program's place: the gap of the token it puts first.
"""
from __future__ import annotations

import numpy as np

from harness.reference import Reference
from harness.spec import load_model


def sample(requests: list, seed: int, min_tokens: int, held=()) -> list:
    """Requests with served tokens: the longest, every one of ``held``
    (those in a slot when the window closed, so that each slot busy then
    is compared), then others in an order drawn from the seed, until
    ``min_tokens`` served tokens are held."""
    served = [r for r in requests if r.generated]
    if not served:
        return []
    longest = max(served, key=lambda r: (len(r.generated), -r.rid))
    out = [longest] + sorted((r for r in held
                              if r.generated and r is not longest),
                             key=lambda r: r.rid)
    rest = [r for r in served if all(r is not o for o in out)]
    order = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 4]
                                  ).permutation(len(rest))
    n = sum(len(r.generated) for r in out)
    for i in order:
        if n >= min_tokens:
            break
        out.append(rest[i])
        n += len(rest[i].generated)
    return out


def _positions(prompt, served):
    """Input sequence and the rows whose logits produced ``served``."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    return seq, rows


def gaps(params, config: dict, requests: list, *, control: bool = False,
         device=None) -> dict:
    """Per request, the gap at every served position: under "program",
    of the served token; with ``control``, also under "control", of the
    control's first choice at the same positions."""
    out = {"program": []}
    if control:
        out["control"] = []
    if not requests:
        return out
    ref = Reference(params, config, load_model(config), device=device)
    served = [np.asarray(r.generated, np.int32) for r in requests]
    pos = [_positions(r.prompt, s) for r, s in zip(requests, served)]
    hidden = ref.hidden([seq for seq, _ in pos],
                        (False, True) if control else (False,))
    head = ref.head()
    for i, (tok, (_, rows)) in enumerate(zip(served, pos)):
        want = np.asarray(ref.logits(hidden[False][i], rows, head))
        best = want.max(-1)
        at = np.arange(len(rows))
        out["program"].append(best - want[at, tok])
        if control:
            pick = np.asarray(ref.logits(hidden[True][i], rows, head,
                                         control=True)).argmax(-1)
            out["control"].append(best - want[at, pick])
    return out


def widest(gap_lists: list) -> float:
    return float(max(float(g.max()) for g in gap_lists)) if gap_lists \
        else float("nan")


def mean(gap_lists: list) -> float:
    return float(np.concatenate(gap_lists).mean()) if gap_lists \
        else float("nan")


def flip_share(gap_lists: list) -> float:
    """Share of the positions whose token is not the reference's best."""
    return float((np.concatenate(gap_lists) > 0).mean()) if gap_lists \
        else float("nan")
