"""The float32 reference against the program, at a size the CPU holds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import adapter, weights
from chipbench.reference import Reference, ssm
from chipbench.tests import tiny

# the program computes in bf16 with float32 norms and softmax: its logits
# lie within a few bf16 steps of the reference's, measured against the
# largest logit
PROGRAM_RTOL = 3e-2


@pytest.mark.parametrize("config", [tiny.DENSE, tiny.SSM],
                         ids=["dense", "ssm"])
def test_reference_matches_program_forward(config):
    params = weights.make(config, 2**32 + 17)
    mdl = adapter.model(config)
    adapter.check_params(mdl, params)
    tokens = np.random.default_rng(0).integers(
        0, config["model"]["vocab"], 37).astype(np.int32)
    got = np.asarray(mdl.apply_train(params, {"tokens": jnp.asarray(
        tokens[None])}, remat=False)[0])
    want = Reference(config).logits(params, tokens, np.arange(len(tokens)))
    assert want.shape == got.shape
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err < PROGRAM_RTOL, err
    assert np.mean(got.argmax(1) == want.argmax(1)) > 0.9


def test_chunked_ssd_is_the_recurrence():
    t, nh, hd, n = 150, 3, 4, 5
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (t, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (t, nh)))
    a = -jnp.exp(jax.random.normal(ks[2], (nh,)))
    b = jax.random.normal(ks[3], (t, n))
    c = jax.random.normal(ks[4], (t, n))

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = h * jnp.exp(dt_t * a)[:, None, None] + (
            dt_t[:, None, None] * x_t[:, :, None] * b_t[None, None, :])
        return h, jnp.einsum("hpn,n->hp", h, c_t,
                             precision=jax.lax.Precision.HIGHEST)
    _, want = jax.lax.scan(step, jnp.zeros((nh, hd, n)), (x, dt, b, c))
    got = ssm.ssd(x, dt, a, b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_lower_precision_moves_the_logits():
    params = weights.make(tiny.DENSE, 5)
    tokens = np.arange(20, dtype=np.int32)
    pos = np.arange(20)
    exact = Reference(tiny.DENSE).logits(params, tokens, pos)
    for quant in ("int8", "fp8"):
        lower = Reference(tiny.DENSE, quant).logits(params, tokens, pos)
        assert 0 < np.max(np.abs(lower - exact)) < np.max(np.abs(exact))
