"""A Mamba-2 layer (arXiv:2405.21060): input projection, causal depthwise
convolution, the selective state-space recurrence with scalar decay per head
and one group of B and C, the SiLU gate with its RMSNorm, and the output
projection, in float32.

The recurrence h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t
+ D x_t is evaluated in its exact chunked (state-space dual) form: within a
chunk as a masked product, across chunks by carrying the state."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import HIGHEST, matmul, rmsnorm

CHUNK = 64
GATE_NORM_EPS = 1e-6


def ssd(x, dt, a, b, c):
    """x (T, H, P), dt (T, H), a (H,), b and c (T, N) → y (T, H, P)."""
    t, nh, hd = x.shape
    n = b.shape[-1]
    q = CHUNK
    pad = -t % q
    x, dt, b, c = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                   for v in (x, dt, b, c))
    nc = (t + pad) // q
    x, dt = x.reshape(nc, q, nh, hd), dt.reshape(nc, q, nh)
    b, c = b.reshape(nc, q, n), c.reshape(nc, q, n)
    cs = jnp.cumsum(dt * a, axis=1)                            # (nc, q, H)
    ein = lambda s, *ops: jnp.einsum(s, *ops, precision=HIGHEST)
    # within a chunk: y_i = sum_{j<=i} (c_i . b_j) exp(cs_i - cs_j) dt_j x_j
    lower = (jnp.arange(q)[:, None] >= jnp.arange(q)[None, :])[None, :, :, None]
    decay = jnp.exp(jnp.where(lower, cs[:, :, None] - cs[:, None], -jnp.inf))
    w = ein("cin,cjn->cij", c, b)[..., None] * decay * dt[:, None]
    y = ein("cijh,cjhp->cihp", w, x)
    # the state each chunk leaves, and the one that enters each chunk
    to_end = jnp.exp(cs[:, -1:] - cs) * dt                      # (nc, q, H)
    left = ein("cjh,cjhp,cjn->chpn", to_end, x, b)
    whole = jnp.exp(cs[:, -1])                                  # (nc, H)

    def carry(h, inp):
        st, dec = inp
        return h * dec[:, None, None] + st, h
    _, enter = jax.lax.scan(carry, jnp.zeros((nh, hd, n), jnp.float32),
                            (left, whole))
    y = y + ein("cin,cih,chpn->cihp", c, jnp.exp(cs), enter)
    return y.reshape(nc * q, nh, hd)[:t]


def layer(m: dict, stacked: dict, x: jax.Array, i, *, quant=None) -> jax.Array:
    p = jax.tree.map(lambda a: a[i], stacked)
    s = m["ssm"]
    t, d = x.shape
    di, n = s["expand"] * d, s["d_state"]
    nh, hd, cw = di // s["head_dim"], s["head_dim"], s["conv_width"]
    f32 = lambda v: v.astype(jnp.float32)
    h = rmsnorm(x, p["ln1"]["w"], m["norm_eps"])
    zxbcdt = matmul(h, p["ssm"]["w_in"], quant)
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * n],
                  zxbcdt[:, 2 * di + 2 * n:])
    padded = jnp.concatenate([jnp.zeros((cw - 1, xbc.shape[1])), xbc])
    conv = f32(p["ssm"]["conv"])
    xbc = jax.nn.silu(sum(padded[k:k + t] * conv[k] for k in range(cw)))
    xs, b, c = xbc[:, :di], xbc[:, di:di + n], xbc[:, di + n:]
    dt = jax.nn.softplus(dt + f32(p["ssm"]["dt_bias"]))
    a = -jnp.exp(f32(p["ssm"]["A_log"]))
    xh = xs.reshape(t, nh, hd)
    y = ssd(xh, dt, a, b, c) + xh * f32(p["ssm"]["D"])[:, None]
    y = rmsnorm(y.reshape(t, di) * jax.nn.silu(z), p["ssm"]["norm"],
                GATE_NORM_EPS)
    return x + matmul(y, p["ssm"]["w_out"], quant)
