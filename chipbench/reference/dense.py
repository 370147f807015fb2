"""A decoder layer with rotary multi-head attention and a gated SiLU MLP
(Llama-style, as MiniCPM publishes it), in float32."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import HIGHEST, matmul, rmsnorm


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding, rotating the two halves of each head: (T, H, D)."""
    t, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(m: dict, stacked: dict, x: jax.Array, i, *, quant=None) -> jax.Array:
    p = jax.tree.map(lambda a: a[i], stacked)
    t = x.shape[0]
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    h = rmsnorm(x, p["ln1"]["w"], m["norm_eps"])
    q = rope(matmul(h, p["attn"]["wq"], quant).reshape(t, hq, hd),
             m["rope_theta"])
    k = rope(matmul(h, p["attn"]["wk"], quant).reshape(t, hkv, hd),
             m["rope_theta"])
    v = matmul(h, p["attn"]["wv"], quant).reshape(t, hkv, hd)
    k, v = jnp.repeat(k, hq // hkv, 1), jnp.repeat(v, hq // hkv, 1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) / jnp.sqrt(
        jnp.float32(hd))
    causal = jnp.arange(t)[None, :, None] >= jnp.arange(t)[None, None, :]
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", a, v, precision=HIGHEST)
    x = x + matmul(o.reshape(t, hq * hd), p["attn"]["wo"], quant)
    h = rmsnorm(x, p["ln2"]["w"], m["norm_eps"])
    g = jax.nn.silu(matmul(h, p["mlp"]["w_gate"], quant)) * matmul(
        h, p["mlp"]["w_up"], quant)
    return x + matmul(g, p["mlp"]["w_down"], quant)
