"""Request schedules, generated from a traffic file and ``--seed``.

Every seed gets the same set of sizes and arrival gaps, in another order:
prompt and output lengths come in blocks of ``LENGTH_VALUES`` that each hold
every fixed value once, and the gaps of an open-loop mix are the quantiles of
the exponential distribution of its rate.  So two seeds offer the same work
and the same load, and differ in the order of arrivals, the pairing of
prompt and output lengths, and the tokens.  The seeding (one child generator
per stream, keyed on the seed) and the exponential gaps follow
``repro.load.traces.ArrivalTrace``.

Prompt lengths take ``LENGTH_VALUES`` fixed values because the engine
compiles one prefill program per distinct prompt length: a continuous
length would put a compile into nearly every admission.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

LENGTH_VALUES = 16
DRAIN_SECONDS = 60.0      # arrivals keep coming while the window's requests finish


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a lognormal length distribution, clipped
    to its range, as whole numbers."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    v = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def _blocks(values: np.ndarray, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` draws made of consecutive blocks, each a permutation of
    ``values``."""
    reps = -(-n // len(values))
    return np.concatenate([rng.permutation(values) for _ in range(reps)])[:n]


def _gaps(rate: float, n: int, rng: np.random.Generator) -> np.ndarray:
    """The ``n`` mid-quantiles of Exp(rate), in an order drawn from ``rng``."""
    p = (np.arange(n) + 0.5) / n
    return rng.permutation(-np.log1p(-p) / rate)


@dataclasses.dataclass(frozen=True)
class Schedule:
    """Requests in the order they are due.  ``due`` is in seconds from the
    window's start; the first ``n_window`` requests, those due inside the
    window, are the ones measured."""
    due: np.ndarray
    prompt_lens: np.ndarray
    output_lens: np.ndarray
    tokens: tuple
    n_window: int

    def __len__(self) -> int:
        return len(self.due)


def prompt_values(traffic: dict) -> np.ndarray:
    """The distinct prompt lengths a mix uses (the shapes to warm up)."""
    return np.unique(quantiles(traffic["prompt"], LENGTH_VALUES))


def schedule(traffic: dict, vocab: int, seed: int, seconds: float) -> Schedule:
    arr = traffic["arrivals"]
    if arr["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    rate = float(arr["rate"])
    n_window = max(1, round(rate * seconds))
    # the window's gaps are scaled to end where the n-th of n evenly spread
    # arrivals would, so every seed has n_window inside it
    g = _gaps(rate, n_window, _rng(seed, 0))
    g *= seconds * n_window / (n_window + 1) / g.sum()
    n_drain = math.ceil(rate * DRAIN_SECONDS)
    due = np.concatenate([np.cumsum(g), seconds + np.cumsum(
        _gaps(rate, n_drain, _rng(seed, 4)))])
    n = n_window + n_drain
    plens = _blocks(quantiles(traffic["prompt"], LENGTH_VALUES), n,
                    _rng(seed, 1))
    olens = _blocks(quantiles(traffic["output"], LENGTH_VALUES), n,
                    _rng(seed, 2))
    tok = _rng(seed, 3)
    tokens = tuple(tok.integers(0, vocab, int(p), dtype=np.int32)
                   for p in plens)
    return Schedule(due=due, prompt_lens=plens, output_lens=olens,
                    tokens=tokens, n_window=n_window)
