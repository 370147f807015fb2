"""Pallas TPU kernel for the Mamba-2 SSD intra-chunk pass.

The SSD algorithm splits into (a) a quadratic attention-like pass inside each
chunk and (b) a linear recurrence across chunk states.  (a) carries ~all the
FLOPs and maps onto the MXU; (b) is a tiny (nh, hd, n) scan that stays in
plain XLA (ops wrapper) — forcing it into the kernel would serialise the
grid for no compute win.  This split is the TPU adaptation of the fused GPU
kernel in the Mamba-2 release.

Kernel, per (batch, chunk, head block) grid cell.  Every operand arrives
head-major, so the kernel never reshapes or transposes the lane dimension
(Mosaic cannot lower either); the (c, n) B/C panels keep the same block index
across a chunk's head blocks, so they are fetched once per chunk:

  scores = C · Bᵀ                (c×c, MXU)
  L      = exp(segsum(dA))       per head (hb, c, c)
  y_diag = (scores ⊙ L_h) · x̄_h  batched over heads (MXU)
  states = (B ⊙ decay)ᵀ · x̄_h    per-chunk outgoing state (hb, n, hd)

VMEM at c=128, hb=8, hd=64, n=128: < 4 MB with double buffering.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HEAD_BLOCK = 8


def _kernel(xh_ref, dcol_ref, drow_ref, decay_ref, b_ref, c_ref,
            ydiag_ref, states_ref, *, chunk: int):
    xh = xh_ref[0, 0]                                # (hb, c, hd)
    dcol = dcol_ref[0, 0]                            # (hb, c, 1) cumsum log-decay
    drow = drow_ref[0, 0]                            # (hb, 1, c)
    decay = decay_ref[0, 0]                          # (hb, c, 1) to chunk end
    B = b_ref[0, 0]                                  # (c, n)
    C = c_ref[0, 0]                                  # (c, n)

    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())))  # (c,c)
    # L[h,i,j] = exp(dacs[i,h] - dacs[j,h]) masked to j<=i
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = (jj <= ii)[None]
    L = jnp.where(tril, jnp.exp(dcol - drow), 0.0)   # (hb, c, c)
    w = scores[None] * L                             # (hb, c, c)
    y = jax.lax.dot_general(w, xh, (((2,), (1,)), ((0,), (0,))))  # (hb,c,hd)
    ydiag_ref[0, 0] = y.astype(ydiag_ref.dtype)

    # outgoing chunk state: states[h] = Σ_j exp(dacs[-1,h]-dacs[j,h]) B_j x̄_jh
    bd = B[None] * decay                             # (hb, c, n)
    st = jax.lax.dot_general(bd, xh, (((1,), (1,)), ((0,), (0,))))  # (hb,n,hd)
    states_ref[0, 0] = st.astype(states_ref.dtype)


def ssd_intra_chunk(xh: jax.Array, dacs: jax.Array, B: jax.Array,
                    C: jax.Array, *,
                    interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """xh: (b, nc, nh, c, hd) dt-scaled inputs; dacs: (b, nc, nh, c) cumsum
    log-decay; B/C: (b, nc, c, n), all float32.
    Returns (y_diag (b, nc, nh, c, hd), states (b, nc, nh, n, hd))."""
    b, nc, nh, c, hd = xh.shape
    n = B.shape[-1]
    hb = math.gcd(nh, HEAD_BLOCK)
    dcol = dacs[..., None]                           # (b, nc, nh, c, 1)
    drow = dacs[..., None, :]                        # (b, nc, nh, 1, c)
    decay = jnp.exp(dacs[..., -1:] - dacs)[..., None]
    heads = lambda *tail: pl.BlockSpec(
        (1, 1, hb) + tail, lambda b, z, h: (b, z, h) + (0,) * len(tail))
    panel = pl.BlockSpec((1, 1, c, n), lambda b, z, h: (b, z, 0, 0))
    y, st = pl.pallas_call(
        functools.partial(_kernel, chunk=c),
        grid=(b, nc, nh // hb),
        in_specs=[heads(c, hd), heads(c, 1), heads(1, c), heads(c, 1),
                  panel, panel],
        out_specs=[heads(c, hd), heads(n, hd)],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, nh, c, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, nh, n, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="ssd_scan",
    )(xh, dcol, drow, decay, B, C)
    return y, st


def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
        C: jax.Array, D: jax.Array, *, chunk: int = 128,
        h0: jax.Array | None = None,
        interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Drop-in replacement for ref.ssd_chunked with the quadratic pass in
    Pallas.  Shapes as in ref.py."""
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
    dA = dtf * A[None, None, None, :]
    dA_cs = jnp.cumsum(dA, axis=2)                    # (b, nc, c, nh)
    xdt = (xf * dtf[..., None]).transpose(0, 1, 3, 2, 4)  # (b, nc, nh, c, hd)

    y_diag, states = ssd_intra_chunk(xdt, dA_cs.transpose(0, 1, 3, 2), Bf,
                                     Cf, interpret=interpret)
    y_diag = y_diag.transpose(0, 1, 3, 2, 4)          # (b, nc, c, nh, hd)
    states = states.transpose(0, 1, 2, 4, 3)          # (b, nc, nh, hd, n)

    # inter-chunk recurrence (tiny, stays in XLA)
    if h0 is None:
        h0 = jnp.zeros((b, nh, hd, n), jnp.float32)
    chunk_decay = jnp.exp(dA_cs[:, :, -1, :])         # (b, nc, nh)

    def step(h, inp):
        st, dec = inp
        return h * dec[..., None, None] + st, h
    h_final, h_in = jax.lax.scan(
        step, h0, (states.swapaxes(0, 1), chunk_decay.swapaxes(0, 1)))
    h_in = h_in.swapaxes(0, 1)                        # (b, nc, nh, hd, n)

    in_decay = jnp.exp(dA_cs)                         # (b, nc, c, nh)
    y_off = jnp.einsum("bzcn,bzch,bzhpn->bzchp", Cf, in_decay, h_in)
    y = y_diag + y_off
    y = y.reshape(b, nc * c, nh, hd)[:, :t]
    y = y + x.astype(jnp.float32)[:, :t] * D[None, None, :, None]
    return y.astype(x.dtype), h_final
