"""Pallas TPU flash-attention (prefill/training) kernel.

TPU-native design (adapted from the GPU flash algorithm):

* Grid = (batch, q_heads, q_blocks, kv_blocks); the kv dimension is the
  innermost ("arbitrary") axis so the online-softmax state lives in VMEM
  scratch across kv steps; batch/head/q axes are parallel (Megacore-safe).
* BlockSpecs tile HBM→VMEM: q/out blocks are (block_q, head_dim), k/v blocks
  (block_k, head_dim); with the default 512×512 bf16 tiles the working set is
  ~1.3 MB — far under the ~16 MB v5e VMEM budget, leaving room for double
  buffering; matmul dims are multiples of 128 to keep the MXU systolic array
  full (head_dim 64/128/256 all align).
* GQA is folded into the k/v index_map (q head h reads kv head h // group) —
  no KV replication in HBM.
* Causality and sliding windows prune whole kv blocks via ``pl.when`` — the
  TPU analogue of the GPU kernel's early-exit, saving real FLOPs, not just
  masking.  ``lengths`` (ragged batches) and ``window`` arrive as
  scalar-prefetch operands so one compiled kernel serves every layer of a
  local:global schedule (gemma3) — window is data, not code.

Validated against ref.attention_naive in tests/test_kernels.py with
interpret=True shape/dtype sweeps.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(lengths_ref, window_ref,            # scalar prefetch
            q_ref, k_ref, v_ref,                # VMEM inputs
            o_ref,                              # VMEM output
            m_ref, l_ref, acc_ref,              # VMEM scratch
            *, causal: bool, block_q: int, block_k: int, q_offset: int,
            scale: float, num_kv_blocks: int):
    b = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    window = window_ref[0]
    length = lengths_ref[b]
    q_lo = q_offset + iq * block_q                   # first absolute q pos
    q_hi = q_lo + block_q - 1
    k_lo = ik * block_k
    k_hi = k_lo + block_k - 1

    run = k_lo < length                              # block has valid keys
    if causal:
        run &= k_lo <= q_hi                          # not fully above diag
    run &= k_hi > q_lo - window                      # not fully out-of-window

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k),
                                               0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k),
                                               1)
        msk = kpos < length
        if causal:
            msk &= kpos <= qpos
        msk &= kpos > qpos - window
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


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | jax.Array | None = None,
                    q_offset: int = 0, lengths: jax.Array | None = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Tq, Hq, D); k/v: (B, Tk, Hkv, D).  Returns (B, Tq, Hq, D)."""
    b, tq, hq, d = q.shape
    _, tk, hkv, _ = k.shape
    g = hq // hkv
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    nq = -(-tq // block_q)
    nk = -(-tk // block_k)
    pad_q, pad_k = nq * block_q - tq, nk * block_k - tk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    # (B, H, T, D) layout: head-major so a (1,1,bq,d) block is contiguous.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    if lengths is None:
        lengths = jnp.full((b,), tk, jnp.int32)
    if window is None:
        window = jnp.array([2 ** 30], jnp.int32)
    else:
        window = jnp.asarray(window, jnp.int32).reshape(1)

    kernel = functools.partial(
        _kernel, causal=causal, block_q=block_q, block_k=block_k,
        q_offset=q_offset, scale=1.0 / math.sqrt(d), num_kv_blocks=nk)

    grid = (b, hq, nq, nk)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d),
                             lambda b, h, iq, ik, *_: (b, h, iq, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b, h, iq, ik, *_: (b, h // g, ik, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda b, h, iq, ik, *_: (b, h // g, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, d),
                                   lambda b, h, iq, ik, *_: (b, h, iq, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_q,), jnp.float32),
                pltpu.VMEM((block_q,), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), window, qt, kt, vt)
    out = out.transpose(0, 2, 1, 3)
    return out[:, :tq]
