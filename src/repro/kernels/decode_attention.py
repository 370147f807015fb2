"""Pallas TPU flash-decode kernel: one new token per sequence against a large
KV cache.

Decode attention is memory-bound (arithmetic intensity ≈ 2 flops/byte of
cache), so the kernel is organised around streaming the cache through VMEM
exactly once:

* Grid = (batch, kv_heads, kv_blocks); kv innermost ("arbitrary") with the
  online-softmax state in VMEM scratch.
* The whole GQA query group (G = Hq/Hkv queries) rides along each kv head —
  the (G, block_k) score panel keeps the MXU busy while the cache streams.
* ``lengths`` (cache fill levels) and ``window`` are scalar-prefetch
  operands; fully-invalid blocks (beyond length, or before the window) are
  pruned with ``pl.when`` so a 1-token decode over a 32k cache with a 1k
  window reads ~1k keys, not 32k.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(lengths_ref, window_ref,
            q_ref, k_ref, v_ref,
            o_ref,
            m_ref, l_ref, acc_ref,
            *, block_k: int, num_kv_blocks: int, scale: float):
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]
    window = window_ref[0]
    k_lo = ik * block_k
    k_hi = k_lo + block_k - 1
    run = (k_lo < length) & (k_hi >= length - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (G, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        msk = (kpos < length) & (kpos >= length - window)
        s = jnp.where(msk, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.where(msk, jnp.exp(s - m_new[:, None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array, *,
                     window: int | jax.Array | None = None,
                     block_k: int = 1024,
                     interpret: bool = False) -> jax.Array:
    """q: (B, 1, Hq, D); caches: (B, S, Hkv, D); lengths: (B,).
    Returns (B, 1, Hq, D)."""
    b, one, hq, d = q.shape
    _, s, hkv, _ = k_cache.shape
    g = hq // hkv
    block_k = min(block_k, s)
    nk = -(-s // block_k)
    pad_k = nk * block_k - s
    kt = k_cache.transpose(0, 2, 1, 3)               # (B, Hkv, S, D)
    vt = v_cache.transpose(0, 2, 1, 3)
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    qg = q[:, 0].reshape(b, hkv, g, d)               # (B, Hkv, G, D)

    if window is None:
        window = jnp.array([2 ** 30], jnp.int32)
    else:
        window = jnp.asarray(window, jnp.int32).reshape(1)

    kernel = functools.partial(_kernel, block_k=block_k, num_kv_blocks=nk,
                               scale=1.0 / math.sqrt(d))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hkv, nk),
            in_specs=[
                pl.BlockSpec((1, 1, g, d), lambda b, h, ik, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b, h, ik, *_: (b, h, ik, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b, h, ik, *_: (b, h, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, d),
                                   lambda b, h, ik, *_: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g,), jnp.float32),
                pltpu.VMEM((g,), jnp.float32),
                pltpu.VMEM((g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), window, qg, kt, vt)
    return out.reshape(b, 1, hq, d)