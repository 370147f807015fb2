"""mamba2-780m: the registered configuration is the published one, and
``ServingEngine`` serves the state-space family as the plain float32
reference of the chip benchmark (``chipbench/reference``) computes it.

The served logits are compared, on seeded random weights at a reduced size
that keeps the published ratios (expand 2, one group of B/C, conv width 4,
chunk smaller than every prompt), with two references:

* the float32 reference, at every served position: the prefill's last
  position, then each decode step through the slot's cache;
* the program's own full forward pass over the same tokens, which does the
  same bf16 operations per token and differs from the served path only in
  how the recurrent state is carried (chunked scan against one step at a
  time, both in float32).  This is the comparison that a state kept in
  bfloat16 fails.
"""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import adapter, spec, weights  # noqa: E402
from chipbench.reference import Reference  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.serving.engine import ServingEngine  # noqa: E402

CONFIG = {
    "arch": "mamba2-reduced", "family": "ssm", "dtype": "bfloat16",
    "model": {"n_layers": 2, "d_model": 64, "n_heads": 0, "n_kv_heads": 0,
              "d_ff": 0, "vocab": 256, "tie_embeddings": True,
              "norm_eps": 1e-5,
              "ssm": {"d_state": 32, "head_dim": 16, "expand": 2,
                      "chunk": 8, "conv_width": 4}},
}
# prompt lengths, none a multiple of the chunk; the second request retires
# first and the fourth is admitted into its slot
PROMPTS = (11, 19, 26, 13)
NEW_TOKENS = (12, 4, 10, 9)

# The program computes in bf16 with float32 norms and state: against the
# float32 reference its logits lie within a few bf16 steps, measured against
# the largest logit of the position (1-2 % here over seeds).
REFERENCE_RTOL = 3e-2
# Against its own full forward pass the served path differs only in the
# state's float32 arithmetic: it reads 0 to 3e-6 over seeds, and a state
# kept in bfloat16 reads 4e-3 to 1e-2 (one rounding of h per decode step).
FORWARD_RTOL = 1e-3


def _serve(params, state_dtype):
    """Serves the requests; returns ``[(request, logits rows)]``, one row
    per served token: the prefill's, then each decode step's."""
    mdl = adapter.model(CONFIG)
    eng = ServingEngine(mdl, params, max_batch=3, max_len=64)
    prefill_rows, decode_rows = [], {}
    prefill_fn, decode = eng._prefill_fn, eng._decode

    def recorded_prefill(plen):
        fn = prefill_fn(plen)

        def run(p, batch):
            logits, pcache = fn(p, batch)
            prefill_rows.append(np.asarray(logits[0, -1], np.float32))
            return logits, pcache
        return run

    def recorded_decode(p, cache, batch):
        logits, cache = decode(p, cache, batch)
        cache = dict(cache, h=cache["h"].astype(state_dtype))
        for s, req in enumerate(eng.slot_req):
            if req is not None:
                decode_rows.setdefault(req.request_id, []).append(
                    np.asarray(logits[s, -1], np.float32))
        return logits, cache

    def write_slot(slot, pcache, plen):
        write(slot, dict(pcache, h=pcache["h"].astype(state_dtype)), plen)

    write = eng._write_slot
    eng._prefill_fn, eng._decode = recorded_prefill, recorded_decode
    eng._write_slot = write_slot
    eng.cache = dict(eng.cache, h=eng.cache["h"].astype(state_dtype))
    rng = np.random.default_rng(7)
    rids = [eng.submit(rng.integers(0, 256, p).astype(np.int32),
                       max_new_tokens=n)
            for p, n in zip(PROMPTS, NEW_TOKENS)]
    done = eng.run_until_done()
    out = []
    for i, rid in enumerate(rids):
        req = done[rid]
        rows = [prefill_rows[i]] + decode_rows.get(rid, [])
        out.append((req, np.stack(rows[:len(req.generated)])))
    return mdl, out


def _rel_err(got, want):
    """Per row: the largest logit difference over the row's largest
    logit."""
    return np.abs(got - want).max(1) / np.abs(want).max(1)


@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16"])
def test_served_logits_match_the_float32_reference(state_dtype):
    params = weights.make(CONFIG, 2**33 + 5)
    mdl, served = _serve(params, jnp.dtype(state_dtype))
    reqs = [r for r, _ in served]
    assert [len(r.generated) for r in reqs] == list(NEW_TOKENS)
    assert len({r.slot for r in reqs[:3]}) == 3        # three slots at once
    assert reqs[3].slot == reqs[1].slot                 # re-admitted slot
    ref = Reference(CONFIG)
    worst_ref = worst_forward = 0.0
    for req, rows in served:
        seq = np.concatenate([req.prompt,
                              np.asarray(req.generated[:-1], np.int32)])
        pos = np.arange(len(req.prompt) - 1, len(seq))
        want = ref.logits(params, seq, pos)
        forward = np.asarray(mdl.apply_train(
            params, {"tokens": jnp.asarray(seq[None])}, remat=False)[0])[pos]
        assert rows.shape == want.shape
        # every served token is the served logits' greedy pick
        assert list(rows.argmax(1)) == req.generated
        worst_ref = max(worst_ref, float(_rel_err(rows, want).max()))
        worst_forward = max(worst_forward,
                            float(_rel_err(rows, forward).max()))
    assert worst_ref < REFERENCE_RTOL, worst_ref
    if state_dtype == "float32":
        assert worst_forward < FORWARD_RTOL, worst_forward
    else:
        assert worst_forward > FORWARD_RTOL, worst_forward


def test_registered_config_is_published_size():
    """48 layers of d_model 1536 and a tied 50288-row vocabulary: 780 M
    parameters, the published count, within 1 %."""
    cfg = get_config("mamba2-780m")
    assert abs(cfg.params_total() - 780e6) < 0.01 * 780e6
    assert cfg.tie_embeddings and cfg.vocab == 50288 and cfg.vocab % 16 == 0
    assert (cfg.n_layers, cfg.d_model, cfg.norm_eps) == (48, 1536, 1e-5)
    s = cfg.ssm
    assert (s.d_state, s.head_dim, s.expand, s.chunk, s.conv_width) == (
        128, 64, 2, 256, 4)
    assert s.n_heads(cfg.d_model) == 48


def test_benchmark_configuration_is_the_registered_one():
    """The chip benchmark's configuration file holds the registered values,
    with nothing cut."""
    config = spec.load_json(spec.ROOT / "chipbench/configs/mamba2-780m.json")
    assert config["reduced"] == [] and config["family"] == "ssm"
    want = get_config("mamba2-780m")
    got = adapter.model(config).cfg
    for f in dataclasses.fields(want):
        if f.name not in ("name", "source"):
            assert getattr(got, f.name) == getattr(want, f.name), f.name
