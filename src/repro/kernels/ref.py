"""Pure-jnp oracles for every kernel in this package.

Three tiers per op:
  * ``*_naive``   — direct einsum/softmax math; the correctness oracle.
  * ``*_blocked`` — the flash/chunked algorithm written in pure jnp
                    (lax.scan over blocks, online softmax / chunked state
                    passing).  Numerically equivalent to naive; used as the
                    default lowering path on CPU dry-runs because it has the
                    kernel's memory profile without requiring Pallas.
  * Pallas kernels in sibling modules are validated against these in
    ``tests/test_kernels.py`` over shape/dtype sweeps.

Shape conventions (throughout the repo):
  q: (B, Tq, Hq, D)   k/v: (B, Tk, Hkv, D)   with Hq % Hkv == 0 (GQA).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gqa_expand(q: jax.Array, n_kv: int) -> jax.Array:
    """(B,T,Hq,D) → (B,T,Hkv,G,D) grouped view for GQA einsums."""
    b, t, hq, d = q.shape
    return q.reshape(b, t, n_kv, hq // n_kv, d)


# --------------------------------------------------------------------------
# Attention — naive oracle
# --------------------------------------------------------------------------

def attention_naive(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    q_offset: int = 0,
                    lengths: jax.Array | None = None) -> jax.Array:
    """Full-materialisation attention.  ``q_offset`` is the absolute position
    of q[0] (for decode/chunked prefill); ``lengths`` (B,) masks the KV
    suffix (serving: per-sequence fill level)."""
    b, tq, hq, d = q.shape
    _, tk, hkv, _ = k.shape
    qg = _gqa_expand(q, hkv)
    scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qpos = q_offset + jnp.arange(tq)[:, None]            # (tq,1)
    kpos = jnp.arange(tk)[None, :]                       # (1,tk)
    mask = jnp.ones((tq, tk), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    if lengths is not None:
        mask = mask[None] & (kpos[None] < lengths[:, None, None])
        mask = mask[:, None, None]                       # (b,1,1,tq,tk)
    else:
        mask = mask[None, None, None]
    s = jnp.where(mask, s, NEG_INF)
    m = s.max(-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)             # 0 on masked rows
    l = p.sum(-1, keepdims=True)
    p = p / jnp.maximum(l, 1e-30)                        # fully-masked row → 0
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(b, tq, hq, d).astype(q.dtype)


# --------------------------------------------------------------------------
# Attention — blocked flash (online softmax), pure jnp
# --------------------------------------------------------------------------

def attention_blocked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: int | None = None,
                      q_offset: int = 0,
                      lengths: jax.Array | None = None,
                      block_q: int = 512, block_k: int = 512) -> jax.Array:
    """Flash algorithm in jnp: scan over q blocks (outer) and kv blocks
    (inner) with running (m, l, acc).  Never materialises Tq×Tk."""
    b, tq, hq, d = q.shape
    _, tk, hkv, _ = k.shape
    g = hq // hkv
    bq = min(block_q, tq)
    bk = min(block_k, tk)
    nq, nk = -(-tq // bq), -(-tk // bk)
    pad_q, pad_k = nq * bq - tq, nk * bk - tk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    scale = 1.0 / math.sqrt(d)
    qb = q.reshape(b, nq, bq, hkv, g, d).astype(jnp.float32)
    kb = k.reshape(b, nk, bk, hkv, d).astype(jnp.float32)
    vb = v.reshape(b, nk, bk, hkv, d).astype(jnp.float32)

    kpos_all = jnp.arange(nk * bk)
    klen = lengths if lengths is not None else jnp.full((b,), tk)

    # The q-block body is checkpointed: without it, reverse-mode AD stores
    # every (bq, bk) probability panel (O(T²) memory — 6+ GB/layer at 4k×12k);
    # with it, backward recomputes the panels flash-style.
    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def q_block(qi, qblk):
        qpos = q_offset + qi * bq + jnp.arange(bq)      # (bq,)

        def kv_step(carry, inp):
            m, l, acc = carry
            kblk, vblk, kpos = inp
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk, kblk) * scale
            msk = jnp.ones((bq, bk), dtype=bool)
            if causal:
                msk &= kpos[None, :] <= qpos[:, None]
            if window is not None:
                msk &= kpos[None, :] > qpos[:, None] - window
            msk = msk[None] & (kpos[None, None, :] < klen[:, None, None])
            msk = msk[:, None, None]
            s = jnp.where(msk, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.where(msk, jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vblk)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, bq), NEG_INF)
        l0 = jnp.zeros((b, hkv, g, bq))
        a0 = jnp.zeros((b, hkv, g, bq, d))
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kb.swapaxes(0, 1), vb.swapaxes(0, 1),
             kpos_all.reshape(nk, bk)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]     # (b,hkv,g,bq,d)
        return out.transpose(0, 3, 1, 2, 4)              # (b,bq,hkv,g,d)

    outs = jax.lax.map(lambda i: q_block(i, qb[:, i]), jnp.arange(nq))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, nq * bq, hq, d)
    return out[:, :tq].astype(q.dtype)


# --------------------------------------------------------------------------
# Decode attention — single new token against a filled KV cache
# --------------------------------------------------------------------------

def decode_attention_naive(q: jax.Array, k_cache: jax.Array,
                           v_cache: jax.Array, lengths: jax.Array, *,
                           window: int | None = None) -> jax.Array:
    """q: (B, 1, Hq, D); caches: (B, S, Hkv, D); lengths: (B,) — number of
    valid cache entries (the new token's position is lengths-1)."""
    b, _, hq, d = q.shape
    _, s, hkv, _ = k_cache.shape
    qg = _gqa_expand(q, hkv)[:, 0]                       # (b,hkv,g,d)
    scale = 1.0 / math.sqrt(d)
    scores = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * scale
    kpos = jnp.arange(s)[None, :]
    msk = kpos < lengths[:, None]
    if window is not None:
        msk &= kpos >= (lengths[:, None] - window)
    msk = msk[:, None, None]
    scores = jnp.where(msk, scores, NEG_INF)
    m = scores.max(-1, keepdims=True)
    p = jnp.where(msk, jnp.exp(scores - m), 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(b, 1, hq, d).astype(q.dtype)


# --------------------------------------------------------------------------
# Mamba-2 SSD — naive recurrence oracle and the chunked (SSD) algorithm
# --------------------------------------------------------------------------

def ssd_naive(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
              C: jax.Array, D: jax.Array,
              h0: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    """Sequential SSM recurrence (the oracle).

    x: (b, t, nh, hd)   dt: (b, t, nh)   A: (nh,) (negative)
    B, C: (b, t, n)     D: (nh,)         h0: (b, nh, hd, n)
    Returns y (b, t, nh, hd), final state (b, nh, hd, n).
    """
    b, t, nh, hd = x.shape
    n = B.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((b, nh, hd, n), dtype=jnp.float32)

    def step(h, inp):
        xt, dtt, Bt, Ct = inp                            # (b,nh,hd),(b,nh),(b,n),(b,n)
        dA = jnp.exp(dtt * A[None, :])                   # (b,nh)
        dBx = jnp.einsum("bn,bhp->bhpn", Bt, xt * dtt[..., None])
        h = h * dA[..., None, None] + dBx
        y = jnp.einsum("bhpn,bn->bhp", h, Ct)
        return h, y

    xs = (x.astype(jnp.float32).swapaxes(0, 1), dt.swapaxes(0, 1),
          B.astype(jnp.float32).swapaxes(0, 1),
          C.astype(jnp.float32).swapaxes(0, 1))
    h, ys = jax.lax.scan(step, h0, xs)
    y = ys.swapaxes(0, 1) + x.astype(jnp.float32) * D[None, None, :, None]
    return y.astype(x.dtype), h


def _segsum(logs: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum(logs[..., j+1:i+1]) for j<=i,
    -inf otherwise (the 1-semiseparable mask of the SSD paper)."""
    t = logs.shape[-1]
    cs = jnp.cumsum(logs, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((t, t), dtype=bool), 0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, D: jax.Array, *, chunk: int = 128,
                h0: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    """State-space duality algorithm (Mamba-2 §6): quadratic attention-like
    compute inside chunks + linear state recurrence across chunks."""
    b, t, nh, hd = x.shape
    n = B.shape[-1]
    c = min(chunk, t)
    nc = -(-t // c)
    pad = nc * c - t
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    xf = x.astype(jnp.float32).reshape(b, nc, c, nh, hd)
    dtf = dt.astype(jnp.float32).reshape(b, nc, c, nh)
    Bf = B.astype(jnp.float32).reshape(b, nc, c, n)
    Cf = C.astype(jnp.float32).reshape(b, nc, c, n)

    dA = dtf * A[None, None, None, :]                    # (b,nc,c,nh) log-decay
    dA_cs = jnp.cumsum(dA, axis=2)                       # within-chunk cumsum
    # 1. intra-chunk (quadratic, the "attention-like" part)
    L = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))       # (b,nc,nh,i,j)
    scores = jnp.einsum("bzin,bzjn->bzij", Cf, Bf)       # (b,nc,i,j)
    xdt = xf * dtf[..., None]                            # x̄ = x·dt
    y_diag = jnp.einsum("bzij,bzhij,bzjhp->bzihp", scores, L, xdt)
    # 2. per-chunk final states
    decay_states = jnp.exp(dA_cs[:, :, -1:, :] - dA_cs)  # (b,nc,c,nh)
    states = jnp.einsum("bzcn,bzch,bzchp->bzhpn", Bf,
                        decay_states, xdt)
    # 3. inter-chunk recurrence
    if h0 is None:
        h0 = jnp.zeros((b, nh, hd, n), dtype=jnp.float32)
    chunk_decay = jnp.exp(dA_cs[:, :, -1, :])            # (b,nc,nh)

    def chunk_step(h, inp):
        st, dec = inp                                    # (b,nh,hd,n),(b,nh)
        h_new = h * dec[..., None, None] + st
        return h_new, h                                  # emit state ENTERING chunk

    (h_final, h_in) = jax.lax.scan(
        chunk_step, h0, (states.swapaxes(0, 1), chunk_decay.swapaxes(0, 1)))
    h_in = h_in.swapaxes(0, 1)                           # (b,nc,nh,hd,n)
    # 4. chunk-input contribution
    in_decay = jnp.exp(dA_cs)                            # (b,nc,c,nh)
    y_off = jnp.einsum("bzcn,bzch,bzhpn->bzchp", Cf, in_decay, h_in)
    y = (y_diag + y_off).reshape(b, nc * c, nh, hd)[:, :t]
    y = y + x.astype(jnp.float32)[:, :t] * D[None, None, :, None]
    return y.astype(x.dtype), h_final


def ssd_decode_update(h: jax.Array, x: jax.Array, dt: jax.Array,
                      A: jax.Array, B: jax.Array) -> jax.Array:
    """One token's state update.  h: (b,nh,hd,n); x: (b,nh,hd); dt: (b,nh);
    B: (b,n).  Returns h_new."""
    dA = jnp.exp(dt * A[None, :])
    dBx = jnp.einsum("bn,bhp->bhpn", B.astype(jnp.float32),
                     x.astype(jnp.float32) * dt[..., None])
    return h * dA[..., None, None] + dBx


def ssd_decode_readout(h_new: jax.Array, x: jax.Array, C: jax.Array,
                       D: jax.Array) -> jax.Array:
    """One token's output from the updated state.  C: (b,n).  Returns y
    (b,nh,hd)."""
    y = jnp.einsum("bhpn,bn->bhp", h_new, C.astype(jnp.float32))
    y = y + x.astype(jnp.float32) * D[None, :, None]
    return y.astype(x.dtype)


def ssd_decode_step(h: jax.Array, x: jax.Array, dt: jax.Array, A: jax.Array,
                    B: jax.Array, C: jax.Array, D: jax.Array
                    ) -> tuple[jax.Array, jax.Array]:
    """One-token SSM update.  h: (b,nh,hd,n); x: (b,nh,hd); dt: (b,nh);
    B,C: (b,n).  Returns (y (b,nh,hd), h_new)."""
    h_new = ssd_decode_update(h, x, dt, A, B)
    return ssd_decode_readout(h_new, x, C, D), h_new
