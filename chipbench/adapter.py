"""Every call the benchmark makes into the program under test.

The surface it relies on, which later changes to the program keep:

* ``repro.models.config.ArchConfig`` / ``SSMSpec`` and
  ``repro.models.build_model`` → ``Model.param_specs``;
* ``repro.serving.engine.ServingEngine(model, params, max_batch=,
  max_len=)``, its ``submit(prompt, max_new_tokens=)`` and ``step()``;
* the engine's ``queue``, ``slot_req`` and ``lengths``, and each
  request's ``prompt`` and ``generated`` list;
* the engine's ``_admit`` and ``_decode`` attributes, which the benchmark
  wraps to time admission and the decode step (and, in a traced run, to
  name them in the profiler's trace).

The engine is built as ``repro.launch.serve.build_engine`` builds it, but
from weights the benchmark made, so that the reference takes nothing the
program made.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from repro.models import build_model
from repro.models.config import ArchConfig, SSMSpec
from repro.serving.engine import ServingEngine


def model(config: dict):
    m = dict(config["model"])
    if "ssm" in m:
        m["ssm"] = SSMSpec(**m["ssm"])
    return build_model(ArchConfig(name=config["arch"],
                                  family=config["family"], **m))


def check_params(mdl, params: dict) -> None:
    """The benchmark's weights must have the program's layout, shapes and
    dtypes."""
    want = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)),
                        mdl.param_specs(jax.numpy.bfloat16))
    got = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), params)
    if want != got:
        raise SystemExit(f"weights do not match the program's parameters:\n"
                         f"program {want}\nbenchmark {got}")


def engine(mdl, params: dict, *, max_batch: int, max_len: int):
    return ServingEngine(mdl, params, max_batch=max_batch, max_len=max_len)


def submit(eng, tokens: np.ndarray, max_new_tokens: int):
    """Queues one request; returns the engine's request object."""
    eng.submit(tokens, max_new_tokens=max_new_tokens)
    return eng.queue[-1]


def step(eng) -> None:
    eng.step()


def busy(eng) -> bool:
    return bool(eng.queue) or any(r is not None for r in eng.slot_req)


def queued(eng) -> int:
    return len(eng.queue)


def in_flight(eng) -> list:
    return [r for r in eng.slot_req if r is not None]


def generated(req) -> list[int]:
    return req.generated


class Hooks:
    """Wraps the engine's admission and decode step.

    ``on_admitted(t)`` runs after every admission pass with the host time at
    which its first tokens exist.  With ``spans`` set, each admission pass
    and decode step is timed and named in the profiler's trace
    (``chipbench.admit`` / ``chipbench.decode``), and each decode step
    records the cache lengths it ran with; the decode step then waits for
    its result, as the engine does right after it."""

    def __init__(self, eng, on_admitted, spans: bool):
        self.spans = spans
        self.admit: list = []        # (t0, t1, [prompt lengths admitted])
        self.decode: list = []       # (t0, t1, lengths per slot, active)
        admit, decode = eng._admit, eng._decode

        def timed_admit():
            before = {id(r) for r in in_flight(eng)}
            t0 = time.perf_counter()
            if spans:
                with jax.profiler.TraceAnnotation("chipbench.admit"):
                    admit()
            else:
                admit()
            t1 = time.perf_counter()
            if spans:
                self.admit.append((t0, t1, [
                    len(r.prompt) for r in in_flight(eng)
                    if id(r) not in before]))
            on_admitted(t1)

        def timed_decode(p, c, b):
            if not spans:
                return decode(p, c, b)
            lengths = np.maximum(eng.lengths, 1).copy()
            active = [r is not None for r in eng.slot_req]
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("chipbench.decode"):
                out = decode(p, c, b)
                jax.block_until_ready(out[0])
            self.decode.append((t0, time.perf_counter(), lengths, active))
            return out

        eng._admit, eng._decode = timed_admit, timed_decode
