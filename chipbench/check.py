"""The comparison that decides ``correct``.

A sample of the requests the window served, drawn from the seed and holding
the longest of them, is run through the float32 reference once each, over
its prompt and its served tokens.  At every served token the reference's
best logit at that position, less the logit of the token that was served,
is the gap by which the served token falls short; the number compared is
the widest gap over the sample.  The first served token comes from the
prefill, the rest from the slot written at admission and the batched decode
steps, so the number covers all three.
"""

from __future__ import annotations

import numpy as np

from .reference import Reference


def sample(served: list[tuple[np.ndarray, list[int]]], seed: int,
           tokens: int) -> list[int]:
    """Indices of served requests: the longest, then others in an order
    drawn from the seed, until ``tokens`` served tokens are covered."""
    if not served:
        return []
    sizes = np.array([len(p) + len(g) for p, g in served])
    longest = int(np.argmax(sizes))
    rest = np.random.default_rng([int(seed), 7]).permutation(len(served))
    picked, count = [longest], len(served[longest][1])
    for i in rest:
        if count >= tokens:
            break
        if i != longest:
            picked.append(int(i))
            count += len(served[i][1])
    return picked


def _rows(ref: Reference, params, prompt, gen):
    seq = np.concatenate([prompt, np.asarray(gen[:-1], np.int32)])
    pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(gen))
    return ref.logits(params, seq, pos)


def widest_gap(ref: Reference, params, served) -> float:
    """Widest gap between the reference's best logit and the served
    token's."""
    worst = 0.0
    for prompt, gen in served:
        rows = _rows(ref, params, prompt, gen)
        got = rows[np.arange(len(gen)), np.asarray(gen)]
        worst = max(worst, float(np.max(rows.max(1) - got)))
    return worst


def control_gap(ref: Reference, lower: Reference, params, served) -> float:
    """The same number for the tokens that ``lower`` (the reference at a
    lower precision) puts first, at each position of the same sequences."""
    worst = 0.0
    for prompt, gen in served:
        rows = _rows(ref, params, prompt, gen)
        pick = _rows(lower, params, prompt, gen).argmax(1)
        got = rows[np.arange(len(gen)), pick]
        worst = max(worst, float(np.max(rows.max(1) - got)))
    return worst
