"""Unified decoder-only LM covering the dense / moe / ssm / hybrid families
(gemma-2b, gemma3-1b, minicpm-2b, mistral-large-123b, mixtral-8x7b,
qwen3-moe-30b-a3b, mamba2-780m, hymba-1.5b).

Design notes (DESIGN.md §7):

* **Scan-over-layers** — parameters are stacked along a leading L axis and the
  stack is applied with ``lax.scan``, so HLO size and compile time are O(1) in
  depth (88-layer/123 B-param configs lower in seconds on the CPU dry-run
  host).
* **Non-uniform attention patterns** (gemma3's 5 local : 1 global) ride the
  same uniform stack: a per-layer ``window`` array is scanned alongside the
  params and feeds the mask arithmetic as a traced scalar (global layers get
  window = seq_len, a no-op).
* Layer bodies are ``jax.checkpoint``-wrapped in training (policy chosen by
  the HiDP local plan — a §Perf knob).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.sharding import ctx as shard_ctx

from . import layers as L
from .config import ArchConfig

CACHE_DTYPE = jnp.bfloat16
#: cache entries held whole per slot (a state-space layer's recurrent state
#: and its convolution's context); decode carries them through the layer
#: scan and updates them in place
STATE_KEYS = ("h", "conv")


@jax.custom_vjp
def _pinned(x):
    """``optimization_barrier`` with a differentiation rule: the primitive
    itself has none, so grad tracing through the scan carry would raise —
    the VJP barriers the cotangent identically, keeping the backward
    residual stream pinned in bf16 too."""
    return jax.lax.optimization_barrier(x)


def _pinned_fwd(x):
    return jax.lax.optimization_barrier(x), None


def _pinned_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


_pinned.defvjp(_pinned_fwd, _pinned_bwd)


# --------------------------------------------------------------------------
# Parameter construction
# --------------------------------------------------------------------------

def layer_param_template(cfg: ArchConfig, key=None, dtype=jnp.float32) -> dict:
    """Parameters of ONE layer (unstacked)."""
    ks = iter(jax.random.split(key, 8)) if key is not None else iter([None] * 8)
    p: dict[str, Any] = {"ln1": L.norm_params(cfg, cfg.d_model)}
    if cfg.family == "ssm":
        p["ssm"] = L.ssm_params(cfg, next(ks), dtype)
        return p
    p["attn"] = L.attn_params(cfg, next(ks), dtype)
    if cfg.family == "hybrid":
        p["ssm"] = L.ssm_params(cfg, next(ks), dtype)
    p["ln2"] = L.norm_params(cfg, cfg.d_model)
    if cfg.family == "moe":
        p["moe"] = L.moe_params(cfg, next(ks), dtype)
    else:
        p["mlp"] = L.mlp_params(cfg, next(ks), dtype)
    return p


def _stack(template_fn, n: int, key=None):
    """Stack n parameter trees along a new leading axis."""
    if key is None:
        t = template_fn(None)
        return jax.tree.map(
            lambda s: (jax.ShapeDtypeStruct((n,) + tuple(s.shape), s.dtype)
                       if isinstance(s, jax.ShapeDtypeStruct)
                       else jax.ShapeDtypeStruct((n,) + s.shape, s.dtype)),
            t)
    return jax.vmap(template_fn)(jax.random.split(key, n))


def init_params(cfg: ArchConfig, key: jax.Array | None = None,
                dtype=jnp.float32) -> dict:
    """Full parameter tree.  key=None → ShapeDtypeStruct tree (dry-run)."""
    ks = jax.random.split(key, 3) if key is not None else [None] * 3
    params = {
        "embed": L.embed_params(cfg, ks[0], dtype),
        "layers": _stack(lambda k: layer_param_template(cfg, k, dtype),
                         cfg.n_layers, ks[1]),
        "final_norm": L.norm_params(cfg, cfg.d_model),
    }
    if key is None:
        params["final_norm"] = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            params["final_norm"])
        params["embed"] = jax.tree.map(
            lambda x: (x if isinstance(x, jax.ShapeDtypeStruct)
                       else jax.ShapeDtypeStruct(x.shape, x.dtype)),
            params["embed"])
    return params


# --------------------------------------------------------------------------
# Per-layer window schedule (the 5:1 local:global pattern etc.)
# --------------------------------------------------------------------------

def window_schedule(cfg: ArchConfig, kv_len: int) -> jax.Array | None:
    """(L,) int32 of per-layer window sizes, or None if no layer is windowed.
    Global layers get kv_len (mask no-op)."""
    if cfg.sliding_window is None:
        return None
    full = jnp.full((cfg.n_layers,), cfg.sliding_window, jnp.int32)
    if cfg.local_global is not None:
        idx = jnp.arange(cfg.n_layers)
        is_global = (idx % (cfg.local_global + 1)) == cfg.local_global
        full = jnp.where(is_global, kv_len, full)
    return full


# --------------------------------------------------------------------------
# KV / SSM cache
# --------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               abstract: bool = False) -> dict:
    """Stacked (leading L) decode cache.  Keys and values (``k``, ``v``)
    change one position a step and pass through the decode's layer scan as
    inputs and outputs; the recurrent state (``STATE_KEYS``) changes whole
    every step and is carried through the scan instead, each layer updating
    its slice in place (see ``forward``)."""
    def mk(shape, dtype=CACHE_DTYPE):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jnp.zeros(shape, dtype)

    cache: dict[str, Any] = {}
    nl = cfg.n_layers
    if cfg.family != "ssm":
        cache["k"] = mk((nl, batch, max_len, cfg.n_kv_heads, cfg.hd))
        cache["v"] = mk((nl, batch, max_len, cfg.n_kv_heads, cfg.hd))
    if cfg.family in ("ssm", "hybrid"):
        s = cfg.ssm
        di, n, nh = s.d_inner(cfg.d_model), s.d_state, s.n_heads(cfg.d_model)
        cache["h"] = mk((nl, batch, nh, s.head_dim, n), jnp.float32)
        cache["conv"] = mk((nl, batch, s.conv_width - 1, di + 2 * n))
    return cache


# --------------------------------------------------------------------------
# Layer application
# --------------------------------------------------------------------------

def apply_layer(cfg: ArchConfig, p: dict, x: jax.Array, *, mode: str,
                positions: jax.Array, window, layer_cache: dict | None,
                lengths: jax.Array | None, moe_impl: str = "dense",
                layer: jax.Array | None = None
                ) -> tuple[jax.Array, dict]:
    """In decode, ``layer_cache`` holds this layer's keys and values and
    the stacked recurrent state of every layer, whose slice ``layer`` is
    updated in place."""
    new_cache: dict[str, Any] = {}
    h = L.apply_norm(cfg, p["ln1"], x)
    if cfg.family == "ssm":
        ssm_cache = (None if layer_cache is None else
                     {"h": layer_cache["h"], "conv": layer_cache["conv"]})
        y, sc = L.mamba_block(cfg, p["ssm"], h, mode=mode, cache=ssm_cache,
                              layer=layer)
        new_cache.update(sc)
        return x + y, new_cache

    attn_cache = (None if layer_cache is None else
                  {"k": layer_cache["k"], "v": layer_cache["v"]})
    a, kv = L.attention(cfg, p["attn"], h, positions=positions, mode=mode,
                        causal=True, window=window, cache=attn_cache,
                        lengths=lengths)
    if kv is not None:
        new_cache.update(kv)
    if cfg.family == "hybrid":
        ssm_cache = (None if layer_cache is None else
                     {"h": layer_cache["h"], "conv": layer_cache["conv"]})
        s, sc = L.mamba_block(cfg, p["ssm"], h, mode=mode, cache=ssm_cache,
                              layer=layer)
        new_cache.update(sc)
        a = (a + s) * 0.5                   # parallel heads, mean-fused
    x = x + a
    h2 = L.apply_norm(cfg, p["ln2"], x)
    if cfg.family == "moe":
        f = L.moe_apply(cfg, p["moe"], h2, impl=moe_impl)
    else:
        f = L.mlp(cfg, p["mlp"], h2)
    return x + f, new_cache


# --------------------------------------------------------------------------
# Full forward passes
# --------------------------------------------------------------------------

def forward(cfg: ArchConfig, params: dict, tokens: jax.Array, *,
            mode: str = "train",
            cache: dict | None = None,
            lengths: jax.Array | None = None,
            moe_impl: str = "dense",
            remat: bool = False,
            remat_group: int = 1,
            logits_tail: int | None = None,
            return_hidden: bool = False) -> tuple[jax.Array, dict | None]:
    """tokens: (B, T) int32.

    mode="train"/"prefill": full sequence; prefill returns the built cache.
    mode="decode": T==1, requires ``cache`` + ``lengths`` (new token position
    = lengths-1).
    The decode's layer scan takes the keys and values as inputs and gives
    their new values as outputs; it carries the recurrent state
    (``STATE_KEYS``) instead, each layer updating its slice of the stack in
    place.  Given as inputs, the state's new value would be written to a
    fresh stacked output, which XLA then copies whole into the donated
    cache.
    ``logits_tail``: only unembed the last N positions (prefill: N=1).
    ``remat_group``: checkpoint every N layers instead of every layer —
    divides saved-activation memory by N at the cost of recomputing up to N
    layers per backward step (a HiDP plan knob for deep, memory-bound
    models).
    """
    b, t = tokens.shape
    x = shard_ctx.constrain_act(
        L.embed(params["embed"], tokens).astype(jnp.bfloat16))
    if mode == "decode":
        assert lengths is not None
        positions = (lengths - 1)[:, None]
        kv_len = cache["k"].shape[2] if "k" in (cache or {}) else t
    else:
        positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
        kv_len = t
    wsched = window_schedule(cfg, kv_len)
    return_cache = mode in ("prefill", "decode")

    cache = cache or {}
    state = {k: a for k, a in cache.items() if k in STATE_KEYS}
    per_layer = {k: a for k, a in cache.items() if k not in STATE_KEYS}

    # window of -1 means "no window" — translate inside via where on mask:
    # the ref kernels accept traced windows; -1 disables via huge value.
    def body(carry, xs):
        x, state = carry
        p, w, lc, i = xs
        w_eff = None if wsched is None else jnp.where(w < 0,
                                                      jnp.int32(2 ** 30), w)
        lc = {**lc, **state} if mode == "decode" else None
        y, nc = apply_layer(cfg, p, x, mode=mode, positions=positions,
                            window=w_eff, layer_cache=lc, lengths=lengths,
                            moe_impl=moe_impl, layer=i)
        y = shard_ctx.constrain_act(y)
        state = {k: nc.pop(k) for k in state}
        return (y, state), (nc if return_cache else None)

    xs = (params["layers"],
          (wsched if wsched is not None
           else jnp.zeros((cfg.n_layers,), jnp.int32) - 1),
          per_layer, jnp.arange(cfg.n_layers, dtype=jnp.int32))
    g = remat_group if (remat and remat_group > 1
                        and cfg.n_layers % remat_group == 0) else 1

    def group_body(carry, xs_g):
        # the barrier pins the checkpointed carry in bf16: without it XLA
        # hoists the backward pass's f32 convert out of the loop and
        # materialises an f32 copy of the whole residual stack (§Perf B)
        x, state = carry
        return jax.lax.scan(body, (_pinned(x), state), xs_g)

    if remat:
        group_body = jax.checkpoint(
            group_body, policy=jax.checkpoint_policies.nothing_saveable)
    xs = jax.tree.map(
        lambda a: a.reshape((cfg.n_layers // g, g) + a.shape[1:]), xs)
    (x, state), new_cache = jax.lax.scan(group_body, (x, state), xs)
    if return_cache:
        new_cache = {**jax.tree.map(
            lambda a: a.reshape((cfg.n_layers,) + a.shape[2:]), new_cache),
            **state}
    x = L.apply_norm(cfg, params["final_norm"], x)
    if logits_tail is not None:
        x = x[:, -logits_tail:]
    if return_hidden:
        return x, (new_cache if return_cache else None)
    logits = shard_ctx.constrain_logits(L.unembed(cfg, params["embed"], x))
    return logits, (new_cache if return_cache else None)
