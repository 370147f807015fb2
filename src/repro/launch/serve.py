"""Serving driver: continuous batching over the HiDP-planned engine on one
TPU chip, with a seeded stream of requests at the model's published width.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --requests 8

It refuses to run anywhere but a TPU: nothing here falls back to the CPU.
Tests build their own (reduced) engines instead.
"""

from __future__ import annotations

import argparse
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.models import build_model
from repro.serving.engine import ServingEngine

REPO = Path(__file__).resolve().parents[3]


def require_tpu() -> jax.Device:
    """The first device, which must be a TPU; raises SystemExit otherwise."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"serving needs a TPU, but JAX's first device is on "
                         f"platform {dev.platform!r}")
    return dev


def place_compile_cache() -> str:
    """Directory of JAX's persistent compilation cache.  JAX reads
    ``JAX_COMPILATION_CACHE_DIR`` itself; only when it is unset is the cache
    placed at the fixed ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_engine(arch: str, *, max_batch: int, max_len: int,
                 seed: int = 0) -> ServingEngine:
    """Full-width ``arch`` with random bf16 weights made from ``seed``."""
    model = build_model(get_config(arch))
    params = model.init(jax.random.PRNGKey(seed), jnp.bfloat16)
    return ServingEngine(model, params, max_batch=max_batch, max_len=max_len)


def seeded_prompts(vocab: int, n: int, lengths: tuple[int, int],
                   seed: int) -> list[np.ndarray]:
    """``n`` random prompts with lengths drawn uniformly from the inclusive
    range ``lengths``."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=int(rng.integers(lengths[0],
                                                         lengths[1] + 1)))
            .astype(np.int32) for _ in range(n)]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=ARCH_IDS)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=1024)
    ap.add_argument("--max-new", type=int, default=32)
    args = ap.parse_args(argv)

    require_tpu()
    place_compile_cache()
    eng = build_engine(args.arch, max_batch=args.max_batch,
                       max_len=args.max_len)
    prompts = seeded_prompts(eng.model.cfg.vocab, args.requests,
                             (16, args.max_len // 2), seed=0)
    t0 = time.perf_counter()
    rids = [eng.submit(p, max_new_tokens=args.max_new) for p in prompts]
    done = eng.run_until_done()
    dt = time.perf_counter() - t0
    toks = sum(len(r.generated) for r in done.values())
    print(f"arch={args.arch}: served {len(done)}/{args.requests} requests, "
          f"{toks} tokens in {dt:.1f}s with {args.max_batch} slots "
          f"(wall time includes compilation)")
    for rid in rids[:3]:
        print(f"  req{rid}: {done[rid].generated[:10]} ...")
    if len(done) != args.requests:
        raise SystemExit(f"only {len(done)} of {args.requests} requests "
                         f"completed")


if __name__ == "__main__":
    main()
