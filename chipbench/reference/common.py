"""Arithmetic shared by the reference's families."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _round(x: jax.Array, axis: int, quant: str | None) -> jax.Array:
    if quant is None:
        return x
    top = {"int8": 127.0, "fp8": 448.0}[quant]
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s > 0, s, 1.0)
    if quant == "int8":
        return jnp.round(x / s) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def matmul(x: jax.Array, w: jax.Array, quant: str | None = None) -> jax.Array:
    """``x @ w`` in float32 for x (..., K) and w (K, N)."""
    x = _round(x.astype(jnp.float32), -1, quant)
    w = _round(w.astype(jnp.float32), 0, quant)
    return jnp.matmul(x, w, precision=HIGHEST)


def rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """RMSNorm with the weight stored as an offset from 1."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w.astype(jnp.float32))
