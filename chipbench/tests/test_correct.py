"""``correct`` at a size the CPU holds: the served program passes, and the
control and each fault the served path can have do not.  The harness's look
for a chip is skipped; everything else is a whole run."""

import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import adapter, harness
from chipbench.reference import Reference
from chipbench.tests import tiny

SECONDS = 1.5


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """One checkout for the module's runs, so they share a compile cache."""
    return tmp_path_factory.mktemp("checkout")


@pytest.fixture(autouse=True)
def _no_chip(monkeypatch):
    monkeypatch.setattr(harness, "require_devices",
                        lambda chips: jax.devices())


def _run(cell, root, seed=2**32 + 3):
    return harness.run(cell, seed, SECONDS, False, time.perf_counter(),
                       root=root)


@pytest.mark.parametrize("config", [tiny.DENSE, tiny.SSM],
                         ids=["dense", "ssm"])
def test_served_program_is_correct(config, root):
    res = _run(tiny.cell(config), root)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("config", [tiny.DENSE, tiny.SSM],
                         ids=["dense", "ssm"])
def test_lower_precision_control_is_not_correct(config, root):
    """The reference in fp8 (int8 does not part from bf16 at this size) put
    in the program's place, judged by the run's own comparison."""
    cell = tiny.cell(config)
    out = harness.serve(cell, 11, SECONDS, False, time.perf_counter(),
                        root=root)
    ref = Reference(config)
    assert harness.judge(out, ref)["correct"]
    control = harness.judge(out, ref, Reference(config, "fp8"))
    assert not control["correct"], control["checks"]


def _stale_state(eng):
    decode = eng._decode

    def run(p, c, b):
        kept = jax.tree.map(jnp.copy, c)
        return decode(p, c, b)[0], kept
    eng._decode = run


def _altered_token(eng):
    decode = eng._decode
    eng._decode = lambda p, c, b: (lambda lg, c2: (jnp.roll(lg, 1, -1), c2))(
        *decode(p, c, b))


def _half_batch(eng):
    decode = eng._decode

    def run(p, c, b):
        logits, c = decode(p, c, b)
        half = logits.shape[0] // 2
        return logits.at[half:].set(logits[:logits.shape[0] - half]), c
    eng._decode = run


def _slot_not_written(eng):
    eng._write_slot = lambda slot, pcache, plen: None


FAULTS = {"state_unchanged": _stale_state, "token_altered": _altered_token,
          "half_batch_left_out": _half_batch,
          "slot_not_written": _slot_not_written}


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("config", [tiny.DENSE, tiny.SSM],
                         ids=["dense", "ssm"])
def test_faults_are_not_correct(monkeypatch, root, config, fault):
    make = adapter.engine

    def faulty(*args, **kw):
        eng = make(*args, **kw)
        FAULTS[fault](eng)
        return eng
    monkeypatch.setattr(adapter, "engine", faulty)
    # every slot is in use, so the half of the batch left out serves
    res = _run(tiny.cell(config, tiny.FULL), root)
    assert not res["correct"], res["checks"]
