"""Plain float32 forward passes, one per family, written from the published
equations (with the program's departures, which each configuration file
lists) and independent of the program: no import of ``repro``, no kernel,
no cache, no batching.  Matrix products run at ``Precision.HIGHEST``, so
float32 stays float32 on the chip.  The model is applied layer by layer,
one prompt at a time, so that it fits beside the served weights.

``quant`` puts a lower precision in: the inputs of every weight product are
rounded to int8 or fp8 (e4m3) with a scale per row of the activations and
per output column of the weights, and the product is taken in float32.
That is the control that ``correct`` has to reject.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import dense, ssm
from .common import matmul, rmsnorm

BUCKET = 256          # sequences are padded to a multiple of this at the end
FAMILIES = {"dense": dense, "ssm": ssm}


class Reference:
    """Logits of one configuration's plain forward pass."""

    def __init__(self, config: dict, quant: str | None = None):
        self.m = config["model"]
        fam = FAMILIES[config["family"]]
        m = self.m
        self._embed = jax.jit(
            lambda p, t: p["embed"]["embedding"][t].astype(jnp.float32))
        self._layer = jax.jit(functools.partial(fam.layer, m, quant=quant))

        def head(p, x, pos):
            x = rmsnorm(x[pos], p["final_norm"]["w"], m["norm_eps"])
            w = (p["embed"]["embedding"].T if m["tie_embeddings"]
                 else p["embed"]["head"])
            return matmul(x, w, quant)
        self._head = jax.jit(head)

    def logits(self, params: dict, tokens: np.ndarray,
               positions: np.ndarray) -> np.ndarray:
        """(K, vocab) float32 logits at ``positions`` of ``tokens``."""
        t = len(tokens)
        padded = np.zeros(-(-t // BUCKET) * BUCKET, np.int32)
        padded[:t] = tokens
        x = self._embed(params, jnp.asarray(padded))
        for i in range(self.m["n_layers"]):
            x = self._layer(params["layers"], x, i)
        return np.asarray(self._head(params, x, jnp.asarray(positions)))
