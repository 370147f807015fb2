"""One run of one cell: build, warm up, serve the window, check, report.

The engine is driven open loop: each request is submitted when it is due,
whether or not earlier ones have finished, and its latency is counted from
when it was due.  Requests due inside the window are followed until they
finish (for at most ``traffic.DRAIN_SECONDS`` more), with arrivals still
coming on schedule meanwhile.
"""

from __future__ import annotations

import dataclasses
import gc
import gzip
import json
import math
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

import jax
import numpy as np

from . import adapter, check, spec, traffic, tracing, weights
from .reference import Reference

WARMUP_NEW_TOKENS = 2


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


def require_devices(chips: int) -> list:
    """The accelerator chips JAX sees; exits without a result where there is
    no TPU or fewer chips than the cell asks for."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"the benchmark needs a TPU, but JAX's first device "
                         f"is on platform {devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX found "
                         f"{len(devices)}")
    return devices


def place_compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout;
    every program is cached, the engine's small eager ones included."""
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with ``q`` percent of the
    sample at or below it."""
    v = sorted(values)
    return v[max(0, math.ceil(q / 100 * len(v)) - 1)]


class Served:
    """What the client saw of the requests it sent: per request of the
    schedule, the engine's request object, the host time of each of its
    tokens, and whether it finished."""

    def __init__(self, sched: traffic.Schedule):
        n = len(sched)
        self.sched = sched
        self.reqs, self.times, self.done = [None] * n, [None] * n, [False] * n
        self.lateness: list[float] = []
        self._index: dict[int, int] = {}
        self._watch: dict[int, object] = {}

    def submitted(self, i: int, req, lateness: float) -> None:
        self.reqs[i], self.times[i] = req, []
        self._index[id(req)] = i
        self.lateness.append(lateness)

    def observe(self, eng, t: float) -> None:
        """Stamps with ``t`` every token that appeared since the last look."""
        now = {id(r): r for r in adapter.in_flight(eng)}
        for key, req in {**self._watch, **now}.items():
            i = self._index.get(key)
            if i is None:
                continue
            gen = adapter.generated(req)
            self.times[i] += [t] * (len(gen) - len(self.times[i]))
            if key not in now:
                self.done[i] = True
        self._watch = now


def _serve(eng, srv: Served, seconds: float, clock,
           tracer=None) -> dict:
    sched = srv.sched
    stats = {"backlog_at_close": None}
    nw = sched.n_window
    nxt, n = 0, len(sched)
    while True:
        now = clock()
        while nxt < n and sched.due[nxt] <= now:
            srv.submitted(nxt, adapter.submit(eng, sched.tokens[nxt],
                                              int(sched.output_lens[nxt])),
                          now - sched.due[nxt])
            nxt += 1
        if tracer:
            tracer.tick(now, adapter.busy(eng))
        if now >= seconds and stats["backlog_at_close"] is None:
            stats["backlog_at_close"] = adapter.queued(eng)
        if now >= seconds and all(srv.done[:nw]):
            break
        if now >= seconds + traffic.DRAIN_SECONDS:
            break
        if adapter.busy(eng):
            adapter.step(eng)
            srv.observe(eng, clock())
        elif nxt < n:
            time.sleep(max(0.0, min(sched.due[nxt] - clock(), 0.005)))
    if tracer:
        tracer.stop()
    return stats


class Tracer:
    """Records a profiler trace for ``length`` seconds, from the first look
    at or after ``at`` seconds into the window at which the engine has work,
    with the benchmark's spans open around it.  Starting only on work makes
    the next step, and so a decode step, fall inside the trace."""

    def __init__(self, at: float, length: float):
        self.at, self.length = at, length
        self.logdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.span = None
        self.t0 = self.t1 = self.started = None

    def tick(self, now: float, busy: bool) -> None:
        if self.t0 is None and now >= self.at and busy:
            jax.profiler.start_trace(self.logdir)
            self.span = jax.profiler.TraceAnnotation("chipbench.loop")
            self.span.__enter__()
            self.t0, self.started = time.perf_counter(), now
        elif self.t0 is not None and now >= self.started + self.length:
            self.stop()

    def stop(self) -> None:
        """Ends the trace, if it runs."""
        if self.t0 is not None and self.t1 is None:
            self.t1 = time.perf_counter()
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()


@dataclasses.dataclass
class Window:
    """What a per-layer metric's reader sees: the traced window, and the
    host's timings of the whole measured window."""
    seconds: float          # the traced window's length on the host's clock
    admits: list            # (t0, t1, [prompt lengths admitted]), traced
    decodes: list           # (t0, t1, lengths per slot, active per slot)
    trace: dict             # tracing.collect output
    t0: float               # the traced window on the trace's clock
    t1: float
    config: dict
    peaks: dict
    # every admission and decode step that started in the measured window
    run_admits: list = dataclasses.field(default_factory=list)
    run_decodes: list = dataclasses.field(default_factory=list)

    @property
    def busy_s(self) -> float:
        return tracing.busy_seconds(self.trace, self.t0, self.t1)

    def calls(self, span: str) -> list:
        return self.admits if span == "admit" else self.decodes

    def kernel_ops(self, kern) -> list:
        """Device events of a kernel's operations inside the benchmark's
        spans of the kind it runs in."""
        spans = [h for h in self.trace["host"] if h[0] == kern.SPAN]
        stats = self.trace.get("op_stats", {})
        ops = [e for e in self.trace["devices"][0]
               if kern.matches(e[0], stats.get(e[0], {}))]
        return tracing.within(ops, spans)


def _window(hooks, tracer: Tracer, config, peaks, start: float,
            seconds: float) -> Window:
    trace = tracing.collect(tracer.logdir)
    shutil.rmtree(tracer.logdir, ignore_errors=True)
    loop = [h for h in trace["host"] if h[0] == "loop"]
    t0, t1 = loop[0][1], loop[0][1] + loop[0][2]
    keep = lambda calls: [c for c in calls
                          if tracer.t0 <= c[0] and c[1] <= tracer.t1]
    run = lambda calls: [c for c in calls
                         if start <= c[0] < start + seconds]
    return Window(seconds=tracer.t1 - tracer.t0, admits=keep(hooks.admit),
                  decodes=keep(hooks.decode), trace=trace, t0=t0, t1=t1,
                  config=config, peaks=peaks, run_admits=run(hooks.admit),
                  run_decodes=run(hooks.decode))


def _end_to_end(cell: spec.Cell, srv: Served, setup_s: float) -> dict:
    nw = srv.sched.n_window
    out = {}
    for m in cell.end_to_end:
        # a suffix after the unit ("itl_p90_ms.short") names the cells that
        # hold the metric to their own bound
        name = m["name"].split(".")[0]
        if name == "setup_s":
            v = setup_s
        elif name.startswith("ttft_p"):
            ttft = [(t[0] - srv.sched.due[i]) * 1e3 if t else math.inf
                    for i, t in enumerate(srv.times[:nw])]
            v = percentile(ttft, float(name[len("ttft_p"):-3]))
        elif name.startswith("itl_p"):
            gaps = [(b - a) * 1e3 for t in srv.times[:nw] if t
                    for a, b in zip(t, t[1:])]
            v = percentile(gaps, float(name[len("itl_p"):-3]))
        else:
            raise SystemExit(f"the harness cannot measure {m['name']!r}")
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def _log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Outcome:
    """A served window, with the engine gone and its weights kept."""
    cell: spec.Cell
    seed: int
    seconds: float
    params: dict
    served: list            # (prompt, served tokens) of finished requests
    srv: Served
    details: dict
    attempted: int
    failed: int
    device: dict
    setup_s: float
    window: Window | None


def serve(cell: spec.Cell, seed: int, seconds: float, trace: bool,
          t_process: float, root: Path = spec.ROOT) -> Outcome:
    """Sets up, serves the window, reads the peak memory and frees the
    engine."""
    devices = require_devices(cell.chips)
    dev = devices[0]
    place_compile_cache(root)
    compiles = CompileLog()
    config, mix = cell.config, cell.traffic

    mdl = adapter.model(config)
    params = weights.make(config, seed)
    adapter.check_params(mdl, params)
    eng = adapter.engine(mdl, params, **mix["engine"])
    sched = traffic.schedule(mix, config["model"]["vocab"], seed, seconds)

    start = [0.0]                       # the window's start, once set up
    clock = lambda: time.perf_counter() - start[0]
    srv = Served(sched)
    hooks = adapter.Hooks(eng, lambda t: srv.observe(eng, t - start[0]),
                          spans=trace)
    rng = np.random.default_rng([seed, 9])
    for plen in traffic.prompt_values(mix):
        adapter.submit(eng, rng.integers(0, config["model"]["vocab"], plen,
                                         dtype=np.int32), WARMUP_NEW_TOKENS)
    while adapter.busy(eng):
        adapter.step(eng)
    hooks.admit.clear()
    hooks.decode.clear()
    setup_compiles = compiles.count

    tracer = (Tracer(at=0.25 * seconds,
                      length=min(mix["trace_seconds"], 0.5 * seconds))
              if trace else None)
    start[0] = time.perf_counter()
    setup_s = start[0] - t_process
    stats = _serve(eng, srv, seconds, clock, tracer)
    window_compiles = compiles.count - setup_compiles
    mem = dev.memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")

    nw = sched.n_window
    finished = [i for i in range(nw) if srv.done[i]]
    window = (_window(hooks, tracer, config, spec.peaks(dev.device_kind),
                      start[0], seconds)
              if trace else None)
    del eng, hooks
    gc.collect()

    lat = srv.lateness
    details = {
        "workload": cell.name, "seed": seed, "seconds": seconds,
        "setup_s": setup_s, "setup_compiles": setup_compiles,
        "compile_seconds": compiles.seconds, "cache_hits": compiles.cache_hits,
        "window_compiles": window_compiles, "memory_peak_bytes": peak,
        "memory_limit_bytes": mem.get("bytes_limit"),
        "requests_in_window": nw, "finished": len(finished),
        "backlog_at_close": stats["backlog_at_close"],
        "generator_late_ms_p50": statistics.median(lat) * 1e3,
        "generator_late_ms_max": max(lat) * 1e3,
    }
    ttft = [(t[0] - sched.due[i]) * 1e3
            for i, t in enumerate(srv.times[:nw]) if t]
    itl = [(b - a) * 1e3 for t in srv.times[:nw] if t
           for a, b in zip(t, t[1:])]
    details.update(ttft_ms_p50=statistics.median(ttft),
                   itl_ms_p50=statistics.median(itl), itl_samples=len(itl))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    return Outcome(cell=cell, seed=seed, seconds=seconds, params=params,
                   served=[(sched.tokens[i],
                            list(adapter.generated(srv.reqs[i])))
                           for i in finished],
                   srv=srv, details=details, attempted=nw,
                   failed=nw - len(finished), device=device, setup_s=setup_s,
                   window=window)


def judge(out: Outcome, ref: Reference,
          lower: Reference | None = None) -> dict:
    """The comparison that decides ``correct``: the widest gap between the
    float32 reference's best logit and the served token's, over a sample of
    the window's finished requests, against the cell's limit.  With
    ``lower`` (the reference at a lower precision, the control) the tokens
    judged are the ones ``lower`` puts first at the same positions."""
    mix = out.cell.traffic
    picked = check.sample(out.served, out.seed, mix["check"]["tokens"])
    sample = [out.served[i] for i in picked]
    gap = (check.widest_gap(ref, out.params, sample) if lower is None
           else check.control_gap(ref, lower, out.params, sample))
    limit = mix["check"]["limit"]
    return {"correct": bool(gap <= limit), "requests": len(picked),
            "tokens": sum(len(g) for _, g in sample),
            "checks": {"max_logit_gap": {"value": gap, "limit": limit}}}


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        t_process: float, root: Path = spec.ROOT) -> dict:
    """One run: the result line's object."""
    out = serve(cell, seed, seconds, trace, t_process, root)
    verdict = judge(out, Reference(cell.config))
    details = dict(out.details, checked_requests=verdict["requests"],
                   checked_tokens=verdict["tokens"])
    for k, v in details.items():
        print(f"{k}: {v}")
    out_dir = root / ".chipbench_runs"
    out_dir.mkdir(parents=True, exist_ok=True)
    stem = out_dir / f"{cell.name}.{seed}.t{int(trace)}"
    nw = out.srv.sched.n_window
    ttft = sorted((t[0] - out.srv.sched.due[i]) * 1e3
                  for i, t in enumerate(out.srv.times[:nw]) if t)
    with open(f"{stem}.json", "w") as f:
        json.dump(dict(details, ttft_ms=ttft), f, indent=1)

    result = {"correct": verdict["correct"], "attempted": out.attempted,
              "failed": out.failed}
    w = out.window
    if w is not None:
        with gzip.open(f"{stem}.trace.json.gz", "wt") as f:
            json.dump(w.trace, f)
        metrics = {}
        for m in cell.per_layer:
            v = spec.reader(m["name"]).read(w)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result.update(metrics=metrics,
                      device=dict(out.device, busy_s=w.busy_s,
                                  window_s=w.t1 - w.t0),
                      breakdown=tracing.breakdown(w.trace, w.t0, w.t1))
    else:
        result.update(metrics=_end_to_end(cell, out.srv, out.setup_s),
                      device=out.device)
    result["checks"] = verdict["checks"]
    gap = verdict["checks"]["max_logit_gap"]
    _log(f"check max_logit_gap: {gap['value']} (limit {gap['limit']}); "
         f"{verdict['tokens']} tokens of {verdict['requests']} requests")
    return result
