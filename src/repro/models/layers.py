"""Building blocks shared by every architecture in the zoo.

All functions are pure: ``params`` pytrees in, arrays out.  Compute runs in
bf16 with fp32 softmax/norm accumulations (TPU-native mixed precision);
params are stored in the dtype the caller chooses (fp32 for training, bf16
for serving).

Attention/SSD hot loops dispatch through ``repro.kernels.ops`` so the same
model code lowers via Pallas on TPU and via the blocked-jnp reference on CPU.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import ops
from .config import ArchConfig, MoESpec, SSMSpec

COMPUTE_DTYPE = jnp.bfloat16


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)) * (1.0 + w)).astype(x.dtype)


def layernorm(x: jax.Array, w: jax.Array, b: jax.Array,
              eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * w + b).astype(x.dtype)


def apply_norm(cfg: ArchConfig, p: Any, x: jax.Array) -> jax.Array:
    if cfg.norm == "layernorm":
        return layernorm(x, p["w"], p["b"], cfg.norm_eps)
    return rmsnorm(x, p["w"], cfg.norm_eps)


def norm_params(cfg: ArchConfig, d: int) -> dict:
    if cfg.norm == "layernorm":
        return {"w": jnp.ones((d,), jnp.float32),
                "b": jnp.zeros((d,), jnp.float32)}
    return {"w": jnp.zeros((d,), jnp.float32)}


# --------------------------------------------------------------------------
# Rotary position embedding
# --------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, T, H, D) with D even; positions: (B, T) absolute indices."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,T,D/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Attention block (self / cross, with optional KV cache)
# --------------------------------------------------------------------------

def attn_params(cfg: ArchConfig, key: jax.Array | None = None,
                dtype=jnp.float32) -> dict:
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    shapes = {"wq": (d, hq * hd), "wk": (d, hkv * hd),
              "wv": (d, hkv * hd), "wo": (hq * hd, d)}
    if key is None:
        return {k: jax.ShapeDtypeStruct(s, dtype) for k, s in shapes.items()}
    ks = jax.random.split(key, len(shapes))
    return {k: (jax.random.normal(kk, s, dtype) / math.sqrt(s[0]))
            for kk, (k, s) in zip(ks, shapes.items())}


def attention(cfg: ArchConfig, p: dict, x: jax.Array, *,
              positions: jax.Array,
              mode: str,
              causal: bool = True,
              window: int | None = None,
              cache: dict | None = None,
              lengths: jax.Array | None = None,
              kv_override: tuple[jax.Array, jax.Array] | None = None,
              ) -> tuple[jax.Array, dict | None]:
    """Self- or cross-attention.

    mode: "full"   — train/prefill over the whole sequence (no cache read);
                     returns (out, new_cache_entry) where the cache entry is
                     the (k, v) computed here (prefill) — caller may discard.
          "decode" — T==1; reads ``cache`` {"k","v"} of shape (B,S,Hkv,hd),
                     writes the new token at ``lengths-1``.
    kv_override: (k, v) already in head layout — cross-attention (whisper
                 decoder / vlm image layers) supplies encoder/image KV.
    """
    b, t, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    xc = x.astype(COMPUTE_DTYPE)
    q = (xc @ p["wq"].astype(COMPUTE_DTYPE)).reshape(b, t, hq, hd)
    if kv_override is None:
        k = (xc @ p["wk"].astype(COMPUTE_DTYPE)).reshape(b, t, hkv, hd)
        v = (xc @ p["wv"].astype(COMPUTE_DTYPE)).reshape(b, t, hkv, hd)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    else:
        k, v = kv_override
        q = rope(q, positions, cfg.rope_theta) if causal else q

    if mode == "decode" and kv_override is None:
        assert cache is not None and lengths is not None
        slot = lengths - 1                                    # (B,)
        bidx = jnp.arange(b)
        k_cache = cache["k"].at[bidx, slot].set(k[:, 0])
        v_cache = cache["v"].at[bidx, slot].set(v[:, 0])
        out = ops.decode_attention(q, k_cache, v_cache, lengths,
                                   window=window)
        new_cache = {"k": k_cache, "v": v_cache}
    elif mode == "decode":                                    # cross, static KV
        kv_len = k.shape[1]
        xl = jnp.full((b,), kv_len) if lengths is None else lengths
        out = ops.decode_attention(q, k, v, xl, window=None)
        new_cache = cache
    else:
        out = ops.flash_attention(q, k, v, causal=causal, window=window,
                                  lengths=lengths)
        new_cache = {"k": k, "v": v}
    out = out.reshape(b, t, hq * hd)
    return (out @ p["wo"].astype(COMPUTE_DTYPE)).astype(x.dtype), new_cache


# --------------------------------------------------------------------------
# MLP (gated / plain)
# --------------------------------------------------------------------------

def mlp_params(cfg: ArchConfig, key=None, dtype=jnp.float32,
               d_ff: int | None = None) -> dict:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    gated = cfg.act in ("swiglu", "geglu")
    shapes = ({"w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}
              if gated else {"w_up": (d, ff), "w_down": (ff, d)})
    if key is None:
        return {k: jax.ShapeDtypeStruct(s, dtype) for k, s in shapes.items()}
    ks = jax.random.split(key, len(shapes))
    return {k: jax.random.normal(kk, s, dtype) / math.sqrt(s[0])
            for kk, (k, s) in zip(ks, shapes.items())}


def _act(cfg: ArchConfig, x: jax.Array) -> jax.Array:
    if cfg.act == "swiglu":
        return jax.nn.silu(x)
    return jax.nn.gelu(x, approximate=True)          # geglu / gelu


def mlp(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    xc = x.astype(COMPUTE_DTYPE)
    if "w_gate" in p:
        h = _act(cfg, xc @ p["w_gate"].astype(COMPUTE_DTYPE)) * (
            xc @ p["w_up"].astype(COMPUTE_DTYPE))
    else:
        h = _act(cfg, xc @ p["w_up"].astype(COMPUTE_DTYPE))
    return (h @ p["w_down"].astype(COMPUTE_DTYPE)).astype(x.dtype)


# --------------------------------------------------------------------------
# Mixture-of-Experts FFN
# --------------------------------------------------------------------------

def moe_params(cfg: ArchConfig, key=None, dtype=jnp.float32) -> dict:
    spec = cfg.moe
    d, e, ffe = cfg.d_model, spec.num_experts, spec.d_ff_expert
    shapes = {"router": (d, e), "w_gate": (e, d, ffe),
              "w_up": (e, d, ffe), "w_down": (e, ffe, d)}
    if key is None:
        return {k: jax.ShapeDtypeStruct(s, dtype) for k, s in shapes.items()}
    ks = jax.random.split(key, len(shapes))
    return {k: jax.random.normal(kk, s, dtype) / math.sqrt(s[-2])
            for kk, (k, s) in zip(ks, shapes.items())}


def moe_router(spec: MoESpec, router_w: jax.Array, x2d: jax.Array
               ) -> tuple[jax.Array, jax.Array]:
    """Top-k routing. Returns (weights (T,k) fp32, indices (T,k) int32)."""
    logits = x2d.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, spec.top_k)
    vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
    return vals, idx


def moe_dense(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    """Baseline MoE: dense all-expert compute + routed combine.

    This is the "P1 / framework default" lowering: robust under any pjit
    sharding (expert axis shards over 'model'), but it computes every expert
    for every token — num_experts/top_k× more FLOPs than active.  The HiDP
    local partitioner replaces it with the EP-a2a path (models/moe_ep.py)
    when expert-partitioning is selected; see EXPERIMENTS.md §Perf.
    """
    spec = cfg.moe
    b, t, d = x.shape
    x2 = x.reshape(b * t, d)
    vals, idx = moe_router(spec, p["router"], x2)
    w = jnp.zeros((b * t, spec.num_experts), jnp.float32)
    w = w.at[jnp.arange(b * t)[:, None], idx].add(vals)     # (T,E)
    xc = x2.astype(COMPUTE_DTYPE)
    gate = jnp.einsum("td,edf->tef", xc, p["w_gate"].astype(COMPUTE_DTYPE))
    up = jnp.einsum("td,edf->tef", xc, p["w_up"].astype(COMPUTE_DTYPE))
    h = _act(cfg, gate) * up
    out_e = jnp.einsum("tef,efd->ted", h, p["w_down"].astype(COMPUTE_DTYPE))
    y = jnp.einsum("ted,te->td", out_e.astype(jnp.float32), w)
    return y.reshape(b, t, d).astype(x.dtype)


def moe_apply(cfg: ArchConfig, p: dict, x: jax.Array, *,
              impl: str = "dense", mesh=None, axis: str = "model"
              ) -> jax.Array:
    if impl == "dense":
        return moe_dense(cfg, p, x)
    from . import moe_ep
    return moe_ep.moe_ep_a2a(
        cfg, p, x, axis=axis,
        a2a_dtype="int8" if impl == "ep_a2a_q8" else "bfloat16")


# --------------------------------------------------------------------------
# Mamba-2 (SSD) block
# --------------------------------------------------------------------------

def ssm_params(cfg: ArchConfig, key=None, dtype=jnp.float32) -> dict:
    spec = cfg.ssm
    d = cfg.d_model
    di, n, nh, cw = (spec.d_inner(d), spec.d_state, spec.n_heads(d),
                     spec.conv_width)
    proj_out = 2 * di + 2 * n + nh                  # z, x, B, C, dt
    shapes = {"w_in": (d, proj_out), "conv": (cw, di + 2 * n),
              "A_log": (nh,), "D": (nh,), "dt_bias": (nh,),
              "norm": (di,), "w_out": (di, d)}
    if key is None:
        return {k: jax.ShapeDtypeStruct(s, dtype) for k, s in shapes.items()}
    ks = jax.random.split(key, len(shapes))
    out = {}
    for kk, (name, s) in zip(ks, shapes.items()):
        if name == "A_log":
            out[name] = jnp.log(jnp.linspace(1.0, 16.0, s[0])).astype(dtype)
        elif name == "D":
            out[name] = jnp.ones(s, dtype)
        elif name == "dt_bias":
            out[name] = jnp.zeros(s, dtype)
        elif name == "norm":
            out[name] = jnp.zeros(s, dtype)
        else:
            out[name] = jax.random.normal(kk, s, dtype) / math.sqrt(s[0])
    return out


def _causal_conv(xbc: jax.Array, w: jax.Array,
                 conv_state: jax.Array | None) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal conv, width cw.  xbc: (B,T,C); w: (cw,C).
    conv_state: (B,cw-1,C) carried context (decode) or None (prefill).
    Returns (out (B,T,C), new_state (B,cw-1,C))."""
    cw = w.shape[0]
    if conv_state is None:
        conv_state = jnp.zeros((xbc.shape[0], cw - 1, xbc.shape[2]),
                               xbc.dtype)
    full = jnp.concatenate([conv_state, xbc], axis=1)        # (B,T+cw-1,C)
    out = sum(full[:, i:i + xbc.shape[1]] * w[i][None, None]
              for i in range(cw))
    new_state = full[:, -(cw - 1):]
    return jax.nn.silu(out), new_state


def mamba_block(cfg: ArchConfig, p: dict, x: jax.Array, *, mode: str,
                cache: dict | None = None, layer: jax.Array | None = None
                ) -> tuple[jax.Array, dict]:
    """One Mamba-2 mixer.  Prefill and training take no cache and return
    this layer's state, {"h": (B,nh,hd,n), "conv": (B,cw-1,C)}.  Decode
    takes the stacked state of every layer, {"h": (L,B,nh,hd,n), "conv":
    (L,B,cw-1,C)}, updates slice ``layer`` of each in place and returns the
    stacks."""
    spec = cfg.ssm
    b, t, d = x.shape
    di, n, nh = spec.d_inner(d), spec.d_state, spec.n_heads(d)
    hd = spec.head_dim
    xc = x.astype(COMPUTE_DTYPE)
    zxbcdt = xc @ p["w_in"].astype(COMPUTE_DTYPE)
    z, xs, B, C, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1)
    conv_in = jnp.concatenate([xs, B, C], axis=-1)
    conv_state = (None if cache is None else
                  jax.lax.dynamic_index_in_dim(cache["conv"], layer,
                                               keepdims=False))
    conv_out, new_conv = _causal_conv(conv_in, p["conv"].astype(COMPUTE_DTYPE),
                                      conv_state)
    xs, B, C = jnp.split(conv_out, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))   # (B,T,nh)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = xs.reshape(b, t, nh, hd)
    if mode == "decode":
        assert cache is not None and layer is not None
        new_conv = jax.lax.dynamic_update_index_in_dim(
            cache["conv"], new_conv, layer, 0)
        y, h_new = ops.ssd_decode_step(cache["h"], layer, xh[:, 0], dt[:, 0],
                                       A, B[:, 0], C[:, 0],
                                       p["D"].astype(jnp.float32))
        y = y[:, None]                                        # (B,1,nh,hd)
    else:
        y, h_new = ops.ssd(xh, dt, A, B, C, p["D"].astype(jnp.float32),
                           chunk=spec.chunk)
    y = y.reshape(b, t, di)
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                p["norm"])
    out = (y.astype(COMPUTE_DTYPE) @ p["w_out"].astype(COMPUTE_DTYPE))
    return out.astype(x.dtype), {"h": h_new, "conv": new_conv}


# --------------------------------------------------------------------------
# Embedding / head
# --------------------------------------------------------------------------

def embed_params(cfg: ArchConfig, key=None, dtype=jnp.float32) -> dict:
    shapes = {"embedding": (cfg.vocab, cfg.d_model)}
    if not cfg.tie_embeddings:
        shapes["head"] = (cfg.d_model, cfg.vocab)
    if key is None:
        return {k: jax.ShapeDtypeStruct(s, dtype) for k, s in shapes.items()}
    ks = jax.random.split(key, len(shapes))
    return {k: jax.random.normal(kk, s, dtype) * 0.02
            for kk, (k, s) in zip(ks, shapes.items())}


def embed(p: dict, tokens: jax.Array) -> jax.Array:
    return p["embedding"][tokens]


def unembed(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    w = p["embedding"].T if cfg.tie_embeddings else p["head"]
    return (x.astype(COMPUTE_DTYPE) @ w.astype(COMPUTE_DTYPE)
            ).astype(jnp.float32)
