"""The program's own spans and named programs, read against the device.

``collect`` reads from the profiler's ``.xplane.pb`` what
``tracing.collect`` leaves out: the engine's host spans (``engine.*``, with
their attributes) and each device's ``XLA Modules`` line, where every
execution of a jitted program is one event named after it
(``jit_engine_prefill``, ``jit_engine_decode``).  The functions after it
turn a window whose ``trace`` holds those two keys, beside what
``tracing.collect`` gives, into per-layer numbers.  Every reader returns
None where the trace has no program spans or no modules, as the trace of
a program that emits neither has none.

Program events are ``[name, start_s, duration_s, {stats}]`` and module
events ``[name, start_s, duration_s]``, on the clock of ``tracing``'s.
"""

from __future__ import annotations

import glob
import os

from . import tracing

PROGRAM_PREFIX = "engine."
#: time with no program span open
HARNESS = "harness"
#: an admission pass and its per-request children
ADMIT = ("engine.admit", "engine.prefill", "engine.write_slot",
         "engine.first_token")
#: a decode step, its children, and the sampling after it
STEP = ("engine.decode", "engine.decode_inputs", "engine.decode_dispatch",
        "engine.decode_wait", "engine.sample")
PREFILL_MODULE = "jit_engine_prefill"
DECODE_MODULE = "jit_engine_decode"


def collect(logdir: str) -> dict:
    """``{"program": [program events], "modules": [[module events] per
    device]}`` from the one trace under ``logdir``."""
    import jax

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {logdir}, found "
                           f"{len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    program, modules = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            modules.append([[e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                            for line in plane.lines
                            if line.name == "XLA Modules"
                            for e in line.events])
        elif plane.name.startswith("/host:"):
            program += [[e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                         {k: v for k, v in e.stats}]
                        for line in plane.lines for e in line.events
                        if e.name.startswith(PROGRAM_PREFIX)]
    return {"program": sorted(program, key=lambda e: e[1]),
            "modules": modules}


def innermost(spans: list) -> list[tuple[float, float, str]]:
    """The time ``spans`` cover, cut into pieces ``(start, end, name)``,
    each named by the innermost span open through it: of the spans open,
    the one opened last (the shortest, where two open at once)."""
    edges = sorted({e[1] for e in spans} | {e[1] + e[2] for e in spans})
    order = sorted(spans, key=lambda e: e[1])
    pieces, open_, i = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while i < len(order) and order[i][1] <= a:
            open_.append(order[i])
            i += 1
        open_ = [e for e in open_ if e[1] + e[2] > a]
        if open_:
            name = max(open_, key=lambda e: (e[1], -e[2]))[0]
            if pieces and pieces[-1][1] == a and pieces[-1][2] == name:
                pieces[-1] = (pieces[-1][0], b, name)
            else:
                pieces.append((a, b, name))
    return pieces


def idle_intervals(trace: dict, t0: float, t1: float) -> list:
    """Intervals in [t0, t1] with no operation on device 0: the gaps that
    ``tracing.idle_gaps`` names by their middle."""
    gaps, last = [], t0
    for s, e in tracing.union(trace["devices"][0]) + [(t1, t1)]:
        if s > last:
            gaps.append((last, min(s, t1)))
        last = max(last, e)
        if last >= t1:
            break
    return gaps


def idle_by_span(trace: dict, t0: float, t1: float) -> dict[str, float]:
    """Device 0's idle seconds in [t0, t1], split over time by the
    innermost program span open at each instant; idle time with none open
    goes to ``harness``.  The values add up to the window's idle time."""
    out: dict[str, float] = {}
    pieces = innermost(trace["program"])
    j = 0
    for a, b in idle_intervals(trace, t0, t1):
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            s, e, name = pieces[k]
            d = min(e, b) - max(s, a)
            if d > 0:
                out[name] = out.get(name, 0.0) + d
                covered += d
            k += 1
        if b - a - covered > 0:
            out[HARNESS] = out.get(HARNESS, 0.0) + (b - a - covered)
    return out


def _has(w) -> bool:
    return bool(w.trace.get("program")) and bool(w.trace.get("modules"))


def spans_started(w, name: str) -> list:
    """Program spans named ``name`` that start in the traced window."""
    return [e for e in w.trace.get("program", ())
            if e[0] == name and w.t0 <= e[1] <= w.t1]


def modules_started(w, prefix: str) -> list:
    """Device 0's executions of the program named ``prefix`` that start in
    the traced window."""
    mods = w.trace.get("modules") or [[]]
    return [e for e in mods[0]
            if e[0].startswith(prefix) and w.t0 <= e[1] <= w.t1]


def _per(total_s: float, n: int) -> float | None:
    return total_s / n * 1e3 if n else None


def prefill_ms(w) -> float | None:
    """Device milliseconds of the prefill program per prefill span."""
    mods = modules_started(w, PREFILL_MODULE)
    if not _has(w) or not mods:
        return None
    return _per(sum(d for _, _, d in mods),
                len(spans_started(w, "engine.prefill")))


def decode_device_ms(w) -> float | None:
    """Device milliseconds of the decode program per decode span."""
    mods = modules_started(w, DECODE_MODULE)
    if not _has(w) or not mods:
        return None
    return _per(sum(d for _, _, d in mods),
                len(spans_started(w, "engine.decode")))


def admit_idle_ms(w) -> float | None:
    """Device-idle milliseconds inside admission (its span or a child's)
    per prefill span."""
    if not _has(w):
        return None
    idle = idle_by_span(w.trace, w.t0, w.t1)
    return _per(sum(idle.get(n, 0.0) for n in ADMIT),
                len(spans_started(w, "engine.prefill")))


def step_idle_ms(w) -> float | None:
    """Device-idle milliseconds inside a decode step and its sampling per
    decode span."""
    if not _has(w):
        return None
    idle = idle_by_span(w.trace, w.t0, w.t1)
    return _per(sum(idle.get(n, 0.0) for n in STEP),
                len(spans_started(w, "engine.decode")))


def admission_split(w) -> dict | None:
    """Where the admission passes that admitted a request spend their
    time, in milliseconds per admitted request: device time of each
    program that ran inside them (clipped to the pass), and device-idle
    time by the innermost span."""
    if not _has(w):
        return None
    passes = [e for e in spans_started(w, "engine.admit")
              if any(p[0] == "engine.prefill" and e[1] <= p[1] <= e[1] + e[2]
                     for p in w.trace["program"])]
    n = len(spans_started(w, "engine.prefill"))
    if not passes or not n:
        return None
    device: dict[str, float] = {}
    for name, s, d in w.trace["modules"][0]:
        for _, a, ad, _ in passes:
            x = min(s + d, a + ad) - max(s, a)
            if x > 0:
                key = name.split("(")[0]
                device[key] = device.get(key, 0.0) + x
    idle: dict[str, float] = {}
    for _, a, ad, _ in passes:
        for k, v in idle_by_span(w.trace, a, a + ad).items():
            idle[k] = idle.get(k, 0.0) + v
    ms = lambda v: round(v / n * 1e3, 3)
    return {"admissions": n,
            "span_ms": ms(sum(e[2] for e in passes)),
            "device_ms": {k: ms(v) for k, v in sorted(
                device.items(), key=lambda kv: -kv[1])},
            "idle_ms": {k: ms(v) for k, v in sorted(
                idle.items(), key=lambda kv: -kv[1])}}
