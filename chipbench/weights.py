"""Random weights for a configuration, made from the seed on the device in
one jitted program, in the type they are served in.  The tree has the
layout of the program's parameters (the adapter checks it against the
program's own description); the benchmark makes it, so the reference that
decides ``correct`` takes nothing the program made."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def key(seed: int) -> jax.Array:
    """A PRNG key for any whole number up to 2**64."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def shapes(config: dict) -> dict:
    """``{path: (shape, kind)}`` of every leaf; ``kind`` says how it is drawn
    and stored: ``matrix`` (normal over sqrt(fan-in)), ``embed``, ``norm``
    (a float32 offset from 1) and the SSM's own ``conv``, ``a_log``, ``d``,
    ``dt_bias`` and ``gate_norm``."""
    m = config["model"]
    L, d, V = m["n_layers"], m["d_model"], m["vocab"]
    out = {"embed/embedding": ((V, d), "embed"),
           "final_norm/w": ((d,), "norm"),
           "layers/ln1/w": ((L, d), "norm")}
    if not m["tie_embeddings"]:
        out["embed/head"] = ((d, V), "matrix")
    if config["family"] == "dense":
        hq, hkv, hd, ff = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["d_ff"]
        out.update({
            "layers/attn/wq": ((L, d, hq * hd), "matrix"),
            "layers/attn/wk": ((L, d, hkv * hd), "matrix"),
            "layers/attn/wv": ((L, d, hkv * hd), "matrix"),
            "layers/attn/wo": ((L, hq * hd, d), "matrix"),
            "layers/ln2/w": ((L, d), "norm"),
            "layers/mlp/w_gate": ((L, d, ff), "matrix"),
            "layers/mlp/w_up": ((L, d, ff), "matrix"),
            "layers/mlp/w_down": ((L, ff, d), "matrix"),
        })
    elif config["family"] == "ssm":
        s = m["ssm"]
        di, n = s["expand"] * d, s["d_state"]
        nh, cw = di // s["head_dim"], s["conv_width"]
        out.update({
            "layers/ssm/w_in": ((L, d, 2 * di + 2 * n + nh), "matrix"),
            "layers/ssm/conv": ((L, cw, di + 2 * n), "conv"),
            "layers/ssm/A_log": ((L, nh), "a_log"),
            "layers/ssm/D": ((L, nh), "d"),
            "layers/ssm/dt_bias": ((L, nh), "dt_bias"),
            "layers/ssm/norm": ((L, di), "gate_norm"),
            "layers/ssm/w_out": ((L, di, d), "matrix"),
        })
    else:
        raise ValueError(f"no weights for family {config['family']!r}")
    return out


def _draw(k, shape, kind):
    normal = lambda: jax.random.normal(k, shape, F32)
    uniform = lambda lo, hi: jax.random.uniform(k, shape, F32, lo, hi)
    if kind == "matrix":
        return normal() / math.sqrt(shape[-2])
    if kind == "embed":
        return normal() * 0.02
    if kind in ("norm", "gate_norm"):
        return normal() * 0.1
    if kind == "conv":
        return normal() / math.sqrt(shape[-2])
    if kind == "a_log":                       # A = -exp(A_log) in [-16, -1]
        return jnp.log(uniform(1.0, 16.0))
    if kind == "d":
        return 1.0 + 0.1 * normal()
    if kind == "dt_bias":                     # softplus(dt_bias) in [1e-3, 1e-1]
        dt = jnp.exp(uniform(math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(kind)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        *parents, last = path.split("/")
        node = tree
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def make(config: dict, seed: int) -> dict:
    """The parameter tree, on the device: norms in float32, the rest in the
    configuration's dtype."""
    dtype = jnp.dtype(config["dtype"])
    leaves = shapes(config)

    def build(k):
        ks = jax.random.split(k, len(leaves))
        flat = {}
        for kk, (path, (shape, kind)) in zip(ks, sorted(leaves.items())):
            x = _draw(kk, shape, kind)
            flat[path] = x if kind == "norm" else x.astype(dtype)
        return _nest(flat)

    return jax.jit(build)(key(seed))

