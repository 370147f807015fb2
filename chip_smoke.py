#!/usr/bin/env python3
"""Chip smoke test: full-width gemma-2b served on one TPU chip through the
normal path (``repro.launch.serve`` → ``ServingEngine`` → jitted prefill and
decode → Pallas kernels), with random bf16 weights made from a seed.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded train step, four chips

On one chip it runs three phases in this process:

* kernels: each Pallas kernel of the served path (flash prefill attention,
  decode attention) against its ``kernels/ref.py`` oracle at the served
  shapes, within ``KERNEL_TOL``;
* serve: 8 seeded requests (prompts of 16-512 tokens, 32 new tokens each)
  through a ``ServingEngine`` with max_batch 8 and max_len 1024; every
  request must complete with 32 tokens and finite logits, and the compiled
  decode step must contain a Pallas kernel (``tpu_custom_call``);
* cross-check: the longest request's served decode logits against a full
  forward without a cache over its prompt and generated tokens, within
  ``LOGIT_TOL``.

With ``--chips 4`` it runs only the sharded DP×TP training step on a 2×2
mesh and the same step on one chip, at gemma-2b widths with the layers cut
to ``TRAIN_LAYERS`` so that the one-chip step fits, and compares their
losses within ``LOSS_RTOL`` and their updated parameters within
``PARAM_ATOL`` (the tolerances of tests/test_distributed.py).

Without a TPU it exits non-zero and prints no result.  Wall times it prints
are smoke timings, not benchmark numbers.  The last line of stdout is one
JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.serve import (build_engine, place_compile_cache,  # noqa: E402
                                require_tpu, seeded_prompts)

ARCH = "gemma-2b"
SEED = 0
MAX_BATCH, MAX_LEN = 8, 1024
N_REQUESTS, NEW_TOKENS, PROMPT_LENS = 8, 32, (16, 512)
# bf16 kernel outputs against the float32 oracle: the bf16 tolerance of
# tests/test_kernels.py, as both atol and rtol
KERNEL_TOL = 2e-2
# served (cached, bf16) decode logits against the uncached full forward, as
# a fraction of the reference's largest logit magnitude
LOGIT_TOL = 5e-2
TRAIN_LAYERS = 2
LOSS_RTOL, PARAM_ATOL = 2e-3, 3e-3


class CompileLog:
    """Counts XLA compilations, their seconds, and persistent-cache hits."""

    def __init__(self):
        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration_secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration_secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __str__(self) -> str:
        return (f"{self.count} compilations, {self.seconds:.1f} s "
                f"({self.cache_hits} from the persistent cache)")


def _max_err(got, want) -> float:
    return float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32))))


def check_kernels(cfg, prompt_lens: list[int], key) -> None:
    """Flash prefill attention at the shortest and longest served prompt,
    and decode attention over the engine's whole cache, against the
    oracles of ``kernels/ref.py`` (full float32 matmuls)."""
    from repro.kernels import decode_attention as da
    from repro.kernels import flash_attention as fa
    from repro.kernels import ref

    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    normal = lambda k, shape: jax.random.normal(k, shape, jnp.bfloat16)
    for t in sorted({min(prompt_lens), max(prompt_lens)}):
        kq, kk, kv, key = jax.random.split(key, 4)
        q, k, v = (normal(kq, (1, t, hq, d)), normal(kk, (1, t, hkv, d)),
                   normal(kv, (1, t, hkv, d)))
        got = jax.jit(fa.flash_attention)(q, k, v)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref.attention_naive)(q, k, v)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=KERNEL_TOL, rtol=KERNEL_TOL)
        print(f"kernel flash_attention T={t} Hq={hq} Hkv={hkv} d={d}: "
              f"max |err| {_max_err(got, want):.3e} "
              f"(tolerance {KERNEL_TOL} abs + rel)")
    kq, kk, kv, kl = jax.random.split(key, 4)
    q = normal(kq, (MAX_BATCH, 1, hq, d))
    kc, vc = (normal(kk, (MAX_BATCH, MAX_LEN, hkv, d)),
              normal(kv, (MAX_BATCH, MAX_LEN, hkv, d)))
    lengths = jax.random.randint(kl, (MAX_BATCH,), 1, MAX_LEN + 1)
    got = jax.jit(da.decode_attention)(q, kc, vc, lengths)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.decode_attention_naive)(q, kc, vc, lengths)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=KERNEL_TOL, rtol=KERNEL_TOL)
    print(f"kernel decode_attention B={MAX_BATCH} S={MAX_LEN} Hq={hq} "
          f"Hkv={hkv} d={d}: max |err| {_max_err(got, want):.3e} "
          f"(tolerance {KERNEL_TOL} abs + rel)")


def serve(eng, prompts: list[np.ndarray], watch: int):
    """Serves every prompt; returns (completed requests, the served decode
    logits of request ``watch``, one row per decode step)."""
    decode = eng._decode
    rows, finite = [], []

    def recording_decode(params, cache, batch):
        logits, cache = decode(params, cache, batch)
        finite.append(jnp.isfinite(logits).all())
        for slot, req in enumerate(eng.slot_req):
            if req is not None and req.request_id == watch:
                rows.append(logits[slot, -1])
        return logits, cache

    eng._decode = recording_decode
    t0 = time.perf_counter()
    rids = [eng.submit(p, max_new_tokens=NEW_TOKENS) for p in prompts]
    done = eng.run_until_done()
    print(f"serve: {len(done)}/{len(prompts)} requests completed in "
          f"{time.perf_counter() - t0:.1f} s (smoke timing, compilation "
          f"included), {eng._decode_steps} decode steps")
    eng._decode = decode
    for rid in rids:
        if rid not in done or len(done[rid].generated) != NEW_TOKENS:
            raise AssertionError(f"request {rid} did not complete with "
                                 f"{NEW_TOKENS} tokens")
    if not all(bool(f) for f in finite):
        raise AssertionError("a decode step produced non-finite logits")
    return done, jnp.stack(rows)


def check_pallas_in_decode(eng) -> None:
    batch = {"tokens": jnp.zeros((eng.max_batch, 1), jnp.int32),
             "lengths": jnp.ones((eng.max_batch,), jnp.int32)}
    hlo = eng._decode.lower(eng.params, eng.cache, batch).compile().as_text()
    if "tpu_custom_call" not in hlo:
        raise AssertionError("the compiled decode step holds no Pallas "
                             "kernel (tpu_custom_call)")
    print("decode step: compiled HLO contains tpu_custom_call")


def cross_check(eng, req, served_rows) -> None:
    """Teacher-forced full forward over prompt + generated tokens: position
    plen + j predicts the token of decode step j."""
    plen = len(req.prompt)
    tokens = np.concatenate([req.prompt, req.generated[:-1]])[None]
    full = jax.jit(lambda p, t: eng.model.apply_train(
        p, {"tokens": t}, remat=False))(eng.params, jnp.asarray(tokens))[0]
    want = full[plen:plen + len(served_rows)]
    if served_rows.shape != want.shape or len(served_rows) != NEW_TOKENS - 1:
        raise AssertionError(f"served {served_rows.shape} vs reference "
                             f"{want.shape} decode logits")
    diff = _max_err(served_rows, want)
    scale = float(jnp.max(jnp.abs(want)))
    agree = np.asarray(jnp.argmax(full[plen - 1:plen - 1 + NEW_TOKENS], -1)
                       ) == np.asarray(req.generated)
    print(f"cross-check request {req.request_id} (prompt {plen} tokens): "
          f"max |served - full forward| decode logit {diff:.4f}, "
          f"reference max |logit| {scale:.4f}, ratio {diff / scale:.4f} "
          f"(tolerance {LOGIT_TOL}); argmax agrees on {int(agree.sum())}/"
          f"{agree.size} generated tokens")
    if not diff <= LOGIT_TOL * scale:
        raise AssertionError("served decode logits differ from the full "
                             "forward beyond tolerance")


def one_chip(dev) -> None:
    compiles = CompileLog()
    cache_dir = place_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind}, "
          f"{len(jax.devices())} visible; compile cache {cache_dir}")
    t0 = time.perf_counter()
    eng = build_engine(ARCH, max_batch=MAX_BATCH, max_len=MAX_LEN, seed=SEED)
    jax.block_until_ready(eng.params)
    cfg = eng.model.cfg
    leaves = jax.tree.leaves(eng.params)
    print(f"model: {cfg.name} full width, {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab}, {cfg.n_heads} q heads / "
          f"{cfg.n_kv_heads} kv head, head_dim {cfg.hd}; parameters "
          f"{sum(x.nbytes for x in leaves)} bytes in "
          f"{sorted({str(x.dtype) for x in leaves})} "
          f"(built in {time.perf_counter() - t0:.1f} s, smoke timing)")
    prompts = seeded_prompts(cfg.vocab, N_REQUESTS, PROMPT_LENS, SEED)
    lens = [len(p) for p in prompts]
    print(f"requests: {N_REQUESTS}, prompt lengths {lens}, "
          f"{NEW_TOKENS} new tokens each")

    check_kernels(cfg, lens, jax.random.PRNGKey(SEED + 1))
    watch = int(np.argmax(lens))
    done, rows = serve(eng, prompts, watch)
    check_pallas_in_decode(eng)
    cross_check(eng, done[watch], rows)
    stats = dev.memory_stats() or {}
    print(f"compiles: {compiles}")
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")


def sharded_train_step(devices) -> None:
    """The DP×TP training step on a 2×2 mesh against the same step on one
    chip, from the same parameters and batch."""
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.sharding import ctx as shard_ctx
    from repro.sharding import specs
    from repro.sharding.plan import MeshDesc, ShardingPlan
    from repro.training import optimizer as optim
    from repro.training.train_loop import make_train_step

    place_compile_cache()
    full = get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=TRAIN_LAYERS)
    print(f"sharded train step: {cfg.name} widths (d_model {cfg.d_model}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab}), layers cut from "
          f"{full.n_layers} to {cfg.n_layers} so that the one-chip step "
          f"fits; float32 parameters and AdamW state")
    model = build_model(cfg)
    ks = jax.random.split(jax.random.PRNGKey(SEED), 3)
    batch = {"tokens": jax.random.randint(ks[1], (8, 128), 0, cfg.vocab),
             "targets": jax.random.randint(ks[2], (8, 128), 0, cfg.vocab)}
    plan = ShardingPlan(arch=cfg.name, shape="smoke",
                        mesh=MeshDesc(("data", "model"), (2, 2)),
                        global_mode="data", local_layout="dp_tp",
                        batch_axes=("data",), tp_axes=("model",),
                        remat=False)
    step = make_train_step(model, optim.OptConfig(lr=1e-3, warmup_steps=1),
                           plan)

    params = model.init(ks[0])
    host_params = jax.device_get(params)
    t0 = time.perf_counter()
    p1, o1, m1 = jax.jit(step, donate_argnums=(0, 1))(
        params, optim.init(params), batch)
    loss1, p1 = float(m1["loss"]), jax.device_get(p1)
    del o1                                   # frees chip 0 for the mesh
    print(f"one chip: loss {loss1:.6f} ({time.perf_counter() - t0:.1f} s, "
          f"smoke timing)")

    mesh = make_mesh((2, 2), ("data", "model"))
    t0 = time.perf_counter()
    with mesh:
        params_s = jax.device_put(
            host_params, specs.param_shardings(mesh, host_params, plan))
        b_sh = specs.batch_shardings(mesh, batch, plan)
        batch_s = {k: jax.device_put(v, b_sh[k]) for k, v in batch.items()}
        with shard_ctx.plan_specs(P("data", None, None),
                                  P("data", None, "model"), mesh=mesh,
                                  ep_axis="model"):
            p2, o2, m2 = jax.jit(step, donate_argnums=(0, 1))(
                params_s, optim.init(params_s), batch_s)
        loss2, p2 = float(m2["loss"]), jax.device_get(p2)
    print(f"2x2 mesh (data, model) on {len(devices)} chips: loss "
          f"{loss2:.6f} ({time.perf_counter() - t0:.1f} s, smoke timing)")
    np.testing.assert_allclose(loss1, loss2, rtol=LOSS_RTOL)
    worst = max(float(np.max(np.abs(np.asarray(a, np.float32)
                                    - np.asarray(b, np.float32))))
                for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    print(f"loss relative difference {abs(loss1 - loss2) / abs(loss1):.3e} "
          f"(tolerance {LOSS_RTOL}); max |param difference| {worst:.3e} "
          f"(tolerance {PARAM_ATOL})")
    if not worst <= PARAM_ATOL:
        raise AssertionError("sharded step's parameters differ from the "
                             "one-chip step's")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    dev = require_tpu()
    devices = jax.devices()
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} TPU "
                         f"chips; JAX found {len(devices)}")
    if args.chips == 4:
        sharded_train_step(devices[:4])
    else:
        one_chip(dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
