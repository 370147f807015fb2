#!/usr/bin/env python3
"""The engine's spans and named programs in a traced window of a cell, and
what the spans cost.

    python3 chipbench/tools/spans.py --workload minicpm-2b.short \
        --seconds 50 --seeds 1 2
    python3 chipbench/tools/spans.py --cost

For each seed it serves the cell as a traced run does (``harness.serve``
with ``trace=True``), and reads the trace twice: as ``tracing.collect``
reads it, and with ``program.collect``'s program spans and module events
added.  One JSON line per seed: the cell's per-layer metrics from both
(they have to agree), ``program``'s readings, the counts of spans against
the benchmark's own timings and of module executions against spans, the
device's idle time split by the innermost program span, and admission's
split.  The merged trace goes to ``.chipbench_runs/``, beside the runs'.

``--cost`` times the spans of one step and of one admitted request with
empty bodies: with no profiler session, inside one, and with a recorder
wired.  The benchmark's own runs never run this.
"""

import argparse
import dataclasses
import gzip
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

DECODE_SPANS = ("engine.decode_inputs", "engine.decode_dispatch",
                "engine.decode_wait")
REQUEST_SPANS = ("engine.prefill", "engine.write_slot", "engine.first_token")


def readings(cell, seed: int, seconds: float) -> dict:
    from chipbench import harness, program, spec, tracing

    base, seen = tracing.collect, {}

    def collect(logdir):
        trace = base(logdir)
        seen["plain"] = dict(trace)
        trace.update(program.collect(logdir))
        return trace

    tracing.collect = collect
    try:
        out = harness.serve(cell, seed, seconds, True, time.perf_counter())
    finally:
        tracing.collect = base
    w = out.window
    plain = dataclasses.replace(w, trace=seen["plain"])
    metrics = {m["name"]: [spec.reader(m["name"]).read(x) for x in (plain, w)]
               for m in cell.per_layer}
    idle = program.idle_by_span(w.trace, w.t0, w.t1)
    window_s = w.t1 - w.t0
    mods = {}
    for name, _, d in program.modules_started(w, ""):
        key = name.split("(")[0]
        n, s = mods.get(key, (0, 0.0))
        mods[key] = (n + 1, s + d)
    counts = {
        "prefill_spans": len(program.spans_started(w, "engine.prefill")),
        "admitted": sum(len(p) for _, _, p in w.admits),
        "prefill_modules": len(program.modules_started(
            w, program.PREFILL_MODULE)),
        "decode_spans": len(program.spans_started(w, "engine.decode")),
        "decodes": len(w.decodes),
        "decode_modules": len(program.modules_started(
            w, program.DECODE_MODULE)),
        "idle_by_span_s": sum(idle.values()),
        "idle_share_x_window_s": (1 - w.busy_s / window_s) * window_s,
    }
    dest = ROOT / ".chipbench_runs"
    dest.mkdir(parents=True, exist_ok=True)
    with gzip.open(dest / f"{cell.name}.{seed}.spans.json.gz", "wt") as f:
        json.dump(w.trace, f)
    return {
        "workload": cell.name, "seed": seed, "window_s": window_s,
        "metrics_plain_vs_merged": metrics,
        "program": {f: getattr(program, f)(w) for f in (
            "prefill_ms", "admit_idle_ms", "decode_device_ms",
            "step_idle_ms")},
        "counts": counts,
        "idle_ms_by_span": {k: round(v * 1e3, 3) for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])},
        "modules": {k: [n, round(s * 1e3, 3)] for k, (n, s) in sorted(
            mods.items(), key=lambda kv: -kv[1][1])},
        "admission": program.admission_split(w),
        "new_programs": sum(int(e[3].get("new_program", 0))
                            for e in w.trace["program"]
                            if e[0] == "engine.prefill"),
        "window_compiles": out.details["window_compiles"],
    }


def _one_step(span, rec, admitted: int) -> None:
    """The spans ``ServingEngine.step`` opens, with nothing inside."""
    with span("engine.step", rec):
        with span("engine.admit", rec):
            for r in range(admitted):
                with span("engine.prefill", rec, request=r, plen=192,
                          queued_ms=1.0, new_program=0):
                    pass
                for c in REQUEST_SPANS[1:]:
                    with span(c, rec, request=r, plen=192):
                        pass
        with span("engine.decode", rec, active=8):
            for c in DECODE_SPANS:
                with span(c, rec):
                    pass
        with span("engine.sample", rec):
            pass


def cost(reps: int = 20000) -> dict:
    """Microseconds per step (no admission) and per admitted request."""
    import jax

    from repro.telemetry import TelemetryRecorder, host_span

    def per_call(rec, admitted):
        _one_step(host_span, rec, admitted)
        t = time.perf_counter()
        for _ in range(reps):
            _one_step(host_span, rec, admitted)
        return (time.perf_counter() - t) / reps * 1e6

    def both(rec_factory):
        step = per_call(rec_factory(), 0)
        return {"step_us": step,
                "request_us": per_call(rec_factory(), 1) - step}

    out = {"device": jax.devices()[0].device_kind,
           "off": both(lambda: None)}
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            out["profiler_on"] = both(lambda: None)
        finally:
            jax.profiler.stop_trace()
    out["recorder"] = both(lambda: TelemetryRecorder("cost"))
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seconds", type=float, default=50)
    ap.add_argument("--seeds", type=int, nargs="*", default=())
    ap.add_argument("--cost", action="store_true")
    args = ap.parse_args(argv)

    if args.cost:
        print(json.dumps({"cost": cost()}), flush=True)
    if args.workload:
        from chipbench import spec
        cell = spec.cell(args.workload)
        for seed in args.seeds:
            print(json.dumps(readings(cell, seed, args.seconds)), flush=True)


if __name__ == "__main__":
    main()
