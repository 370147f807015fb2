"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) and blocked-jnp
implementations vs. the pure-jnp naive oracles in kernels/ref.py."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest  # noqa: F401

from _hypothesis_compat import given, settings, st

from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref, ssd_scan

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _mk_qkv(key, b, tq, tk, hq, hkv, d, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, tq, hq, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, tk, hkv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, tk, hkv, d)).astype(dtype)
    return q, k, v


SHAPES = [
    # (b, tq, tk, hq, hkv, d, window, causal, bq, bk)
    (1, 128, 128, 4, 4, 64, None, True, 64, 64),
    (2, 64, 64, 8, 2, 32, None, True, 16, 32),
    (2, 37, 53, 6, 3, 16, 12, True, 16, 16),
    (1, 32, 32, 4, 1, 128, None, False, 32, 16),
    (3, 1, 96, 8, 4, 64, None, True, 16, 32),
    (2, 80, 80, 5, 5, 48, 24, True, 32, 32),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", SHAPES)
def test_flash_attention_pallas_vs_oracle(shape, dtype, rng):
    b, tq, tk, hq, hkv, d, win, caus, bq, bk = shape
    q, k, v = _mk_qkv(rng, b, tq, tk, hq, hkv, d, dtype)
    lens = jnp.asarray([tk] + [max(tk * 2 // 3, 1)] * (b - 1))
    want = ref.attention_naive(q, k, v, causal=caus, window=win,
                               q_offset=tk - tq, lengths=lens)
    got = fa.flash_attention(q, k, v, causal=caus, window=win,
                             q_offset=tk - tq, lengths=lens,
                             block_q=bq, block_k=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("shape", SHAPES[1:3])
def test_flash_attention_tpu_dispatch_gradient(shape, rng, monkeypatch):
    """On TPU ``ops`` runs the Pallas kernel forward and takes the gradient
    from the blocked algorithm; both must match the oracle."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(fa, "flash_attention",
                        functools.partial(fa.flash_attention, interpret=True))
    b, tq, tk, hq, hkv, d, win, caus, bq, bk = shape
    q, k, v = _mk_qkv(rng, b, tq, tk, hq, hkv, d, jnp.float32)
    lens = jnp.asarray([tk] + [max(tk * 2 // 3, 1)] * (b - 1))
    kw = dict(causal=caus, window=win, q_offset=tk - tq, lengths=lens)
    w = jax.random.normal(jax.random.PRNGKey(1), (b, tq, hq, d))
    loss = lambda f: lambda q, k, v: jnp.sum(f(q, k, v) * w)
    got = jax.value_and_grad(loss(functools.partial(
        ops.flash_attention, block_q=bq, block_k=bk, **kw)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(loss(functools.partial(
        ref.attention_naive, **kw)), argnums=(0, 1, 2))(q, k, v)
    for g, w_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w_),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", SHAPES)
def test_flash_attention_blocked_vs_oracle(shape, dtype, rng):
    b, tq, tk, hq, hkv, d, win, caus, bq, bk = shape
    q, k, v = _mk_qkv(rng, b, tq, tk, hq, hkv, d, dtype)
    lens = jnp.asarray([tk] + [max(tk // 2, 1)] * (b - 1))
    want = ref.attention_naive(q, k, v, causal=caus, window=win,
                               q_offset=tk - tq, lengths=lens)
    got = ref.attention_blocked(q, k, v, causal=caus, window=win,
                                q_offset=tk - tq, lengths=lens,
                                block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


DECODE_SHAPES = [
    (2, 128, 8, 2, 64, None, 32),
    (3, 96, 4, 4, 32, 24, 32),
    (1, 64, 8, 1, 128, None, 64),
    (4, 256, 12, 3, 64, 100, 128),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", DECODE_SHAPES)
def test_decode_attention_pallas_vs_oracle(shape, dtype, rng):
    b, s, hq, hkv, d, win, bk = shape
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (b, 1, hq, d)).astype(dtype)
    kc = jax.random.normal(ks[1], (b, s, hkv, d)).astype(dtype)
    vc = jax.random.normal(ks[2], (b, s, hkv, d)).astype(dtype)
    lens = jnp.asarray([s] + [max(s // 3, 1)] * (b - 1))
    want = ref.decode_attention_naive(q, kc, vc, lens, window=win)
    got = da.decode_attention(q, kc, vc, lens, window=win, block_k=bk,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


# --------------------------------------------------------------------------
# SSD
# --------------------------------------------------------------------------

def _mk_ssd(key, b, t, nh, hd, n):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, t, nh, hd)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, nh))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)))
    B = jax.random.normal(ks[3], (b, t, n)) * 0.3
    C = jax.random.normal(ks[4], (b, t, n)) * 0.3
    D = jnp.full((nh,), 0.1)
    return x, dt, A, B, C, D


SSD_SHAPES = [(1, 64, 4, 8, 16, 16), (2, 48, 2, 16, 8, 8),
              (1, 33, 3, 8, 4, 16), (2, 128, 8, 16, 32, 32)]


@pytest.mark.parametrize("shape", SSD_SHAPES)
def test_ssd_chunked_vs_naive(shape, rng):
    b, t, nh, hd, n, chunk = shape
    args = _mk_ssd(rng, b, t, nh, hd, n)
    y0, h0 = ref.ssd_naive(*args)
    y1, h1 = ref.ssd_chunked(*args, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0), atol=1e-4)


@pytest.mark.parametrize("shape", SSD_SHAPES)
def test_ssd_pallas_vs_naive(shape, rng):
    b, t, nh, hd, n, chunk = shape
    args = _mk_ssd(rng, b, t, nh, hd, n)
    y0, h0 = ref.ssd_naive(*args)
    y1, h1 = ssd_scan.ssd(*args, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0), atol=1e-4)


def test_ssd_decode_matches_scan_tail(rng):
    b, t, nh, hd, n = 2, 48, 4, 8, 16
    x, dt, A, B, C, D = _mk_ssd(rng, b, t, nh, hd, n)
    y_full, h_full = ref.ssd_naive(x, dt, A, B, C, D)
    _, h_prefix = ref.ssd_naive(x[:, :-1], dt[:, :-1], A, B[:, :-1],
                                C[:, :-1], D)
    y_last, h_last = ref.ssd_decode_step(h_prefix, x[:, -1], dt[:, -1], A,
                                         B[:, -1], C[:, -1], D)
    np.testing.assert_allclose(np.asarray(y_last), np.asarray(y_full[:, -1]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_last), np.asarray(h_full),
                               atol=1e-5)


def test_ssd_state_carry_composes(rng):
    """Chunked prefill of [0:t1] then [t1:t] == one pass (h0 handoff)."""
    b, t, nh, hd, n = 1, 64, 2, 8, 8
    x, dt, A, B, C, D = _mk_ssd(rng, b, t, nh, hd, n)
    y_full, h_full = ref.ssd_chunked(x, dt, A, B, C, D, chunk=16)
    t1 = 32
    y1, h1 = ref.ssd_chunked(x[:, :t1], dt[:, :t1], A, B[:, :t1], C[:, :t1],
                             D, chunk=16)
    y2, h2 = ref.ssd_chunked(x[:, t1:], dt[:, t1:], A, B[:, t1:], C[:, t1:],
                             D, chunk=16, h0=h1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full), atol=1e-4)


# --------------------------------------------------------------------------
# property sweep: random shapes through blocked vs naive
# --------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(1, 3), st.integers(1, 72), st.integers(1, 72),
       st.sampled_from([(4, 4), (4, 2), (8, 1), (6, 3)]),
       st.sampled_from([16, 32, 64]),
       st.booleans())
def test_attention_property_sweep(b, tq, tk, heads, d, causal):
    tk = max(tk, tq)                     # decode-style or square
    hq, hkv = heads
    key = jax.random.PRNGKey(tq * 1000 + tk)
    q, k, v = _mk_qkv(key, b, tq, tk, hq, hkv, d, jnp.float32)
    want = ref.attention_naive(q, k, v, causal=causal, q_offset=tk - tq)
    got = ref.attention_blocked(q, k, v, causal=causal, q_offset=tk - tq,
                                block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)
