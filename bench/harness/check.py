"""The comparison that decides ``correct`` for a served model.

After the window has closed, a sample of the requests drawn from the seed
(the one with the most served tokens always in it, then others until the
sample holds ``min_tokens`` served tokens) is run through the reference
once, over each prompt followed by its served tokens.  At the position that
produced each served token, the gap is how far the served token's logit
lies below the reference's best logit there.  Greedy decoding with no
error gives 0; a near-tie that rounding flipped gives a small gap.  The
widest gap, the mean gap and the share of positions with a gap are read
over the sample; the cell's limits file names those compared.

The control reads the same positions with the int8 reference in the
program's place: the gap of the token it puts first.
"""
from __future__ import annotations

import numpy as np

from harness.reference import Reference


def sample(requests: list, seed: int, min_tokens: int) -> list:
    """Requests with served tokens: the longest, then others in an order
    drawn from the seed, until ``min_tokens`` served tokens are held."""
    served = [r for r in requests if r.generated]
    if not served:
        return []
    longest = max(served, key=lambda r: (len(r.generated), -r.rid))
    rest = [r for r in served if r is not longest]
    order = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 4]
                                  ).permutation(len(rest))
    out, n = [longest], len(longest.generated)
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
    ref = Reference(params, config, device=device)
    ctl = (Reference(params, config, device=device, control=True)
           if control else None)
    out = {"program": []}
    if ctl is not None:
        out["control"] = []
    for r in requests:
        served = np.asarray(r.generated, np.int32)
        seq, rows = _positions(r.prompt, served)
        want = np.asarray(ref.logits(ref.hidden(seq), rows))
        best = want.max(-1)
        at = np.arange(len(rows))
        out["program"].append(best - want[at, served])
        if ctl is not None:
            pick = np.asarray(ctl.logits(ctl.hidden(seq), rows)).argmax(-1)
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
