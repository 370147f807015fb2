"""Dispatch from the models to the kernels; the platform picks the lowering.

  * on TPU — the compiled Pallas kernels (``flash_attention``,
    ``decode_attention``, ``ssd_scan``);
  * anywhere else — the blocked jnp algorithms of ``ref.py``, which have the
    kernels' memory profile and lower on every backend.

Interpret mode is never chosen here: tests that exercise a Pallas kernel off
the chip call it with ``interpret=True`` themselves.  The full-materialisation
oracles in ``ref.py`` are the tests' reference only.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from repro.sharding import ctx as shard_ctx

from . import decode_attention as da
from . import flash_attention as fa
from . import ref, ssd_scan


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pallas(kernel, blocked, batched: tuple, shared: tuple = ()):
    """``kernel(*batched, *shared)``, made to work where Pallas alone does not.

    * Pallas gives a kernel no reverse-mode rule: the gradient is that of
      ``blocked``, which computes the same function with the same arguments.
    * XLA cannot partition a Mosaic kernel: under a plan whose mesh spans
      several devices, the kernel runs per batch shard inside ``shard_map``
      (``batched`` split along their leading axis like the plan's
      activations, ``shared`` replicated).
    """
    mesh, act = shard_ctx.get_mesh(), shard_ctx.get_act_spec()
    if mesh is not None and mesh.size > 1 and act is not None:
        rows = P(act[0])
        kernel = jax.shard_map(
            kernel, mesh=mesh, out_specs=rows, check_vma=False,
            in_specs=(rows,) * len(batched) + (P(),) * len(shared))
    fn = jax.custom_vjp(kernel)
    fn.defvjp(lambda *args: (kernel(*args), args),
              lambda args, g: jax.vjp(blocked, *args)[1](g))
    return fn(*batched, *shared)


# --------------------------------------------------------------------------
# Attention (prefill / training)
# --------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                    lengths=None, block_q=512, block_k=512):
    def attend(impl):
        return lambda q, k, v, lengths, window: impl(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            lengths=lengths, block_q=block_q, block_k=block_k)

    blocked = attend(ref.attention_blocked)
    if not _on_tpu():
        return blocked(q, k, v, lengths, window)
    return _pallas(attend(fa.flash_attention), blocked, (q, k, v, lengths),
                   (window,))


# --------------------------------------------------------------------------
# Decode attention (one token vs. KV cache)
# --------------------------------------------------------------------------

def decode_attention(q, k_cache, v_cache, lengths, *, window=None,
                     block_k=1024):
    blocked = lambda q, k, v, lengths, window: ref.decode_attention_naive(
        q, k, v, lengths, window=window)
    if not _on_tpu():
        return blocked(q, k_cache, v_cache, lengths, window)
    kernel = lambda q, k, v, lengths, window: da.decode_attention(
        q, k, v, lengths, window=window, block_k=block_k)
    return _pallas(kernel, blocked, (q, k_cache, v_cache, lengths), (window,))


# --------------------------------------------------------------------------
# Mamba-2 SSD
# --------------------------------------------------------------------------

def ssd(x, dt, A, B, C, D, *, chunk=128, h0=None):
    """Chunked SSD scan (prefill/training), named ``ssd_scan`` in the
    program's operations."""
    def scan(impl):
        return lambda x, dt, B, C, h0, A, D: impl(x, dt, A, B, C, D,
                                                  chunk=chunk, h0=h0)

    blocked = scan(ref.ssd_chunked)
    with jax.named_scope("ssd_scan"):
        if not _on_tpu():
            return blocked(x, dt, B, C, h0, A, D)
        return _pallas(scan(ssd_scan.ssd), blocked, (x, dt, B, C, h0),
                       (A, D))


def ssd_decode_step(h, x, dt, A, B, C, D):
    """One token's state update and readout, named ``ssd_decode_step`` in
    the program's operations."""
    with jax.named_scope("ssd_decode_step"):
        return ref.ssd_decode_step(h, x, dt, A, B, C, D)
