"""From a profiler trace to device numbers.

``collect`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
events (and, for each device operation's name, the statistics the profiler
gives its first occurrence); everything after that works on those events
alone, so the tests run the same reduction on events written by hand.

Events are ``[name, start_s, duration_s]`` on one clock, in seconds from the
trace's start.  Device operations come from the ``XLA Ops`` line of each
device plane; host spans are the benchmark's own ``chipbench.*``
annotations.
"""

from __future__ import annotations

import glob
import os

HOST_PREFIX = "chipbench."


def collect(logdir: str) -> dict:
    """``{"devices": [[op events] per device], "host": [span events],
    "op_stats": {operation name: {statistic: value}}}``."""
    import jax

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {logdir}, found "
                           f"{len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    devices, host, stats = [], [], {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    ops.append([e.name, e.start_ns * 1e-9,
                                e.duration_ns * 1e-9])
                    if e.name not in stats:
                        stats[e.name] = {k: str(v) for k, v in e.stats}
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            host += [[e.name[len(HOST_PREFIX):], e.start_ns * 1e-9,
                      e.duration_ns * 1e-9]
                     for line in plane.lines for e in line.events
                     if e.name.startswith(HOST_PREFIX)]
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1]),
            "op_stats": stats}


def union(events: list) -> list[tuple[float, float]]:
    """The merged intervals that ``events`` cover."""
    out: list[list[float]] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return [tuple(iv) for iv in out]


def busy_seconds(trace: dict, t0: float, t1: float) -> float:
    """Seconds in [t0, t1] in which an operation ran, averaged over the
    devices."""
    total = 0.0
    for ops in trace["devices"]:
        total += sum(max(0.0, min(e, t1) - max(s, t0))
                     for s, e in union(ops))
    return total / max(1, len(trace["devices"]))


def idle_gaps(trace: dict, t0: float, t1: float) -> list[tuple[str, float]]:
    """Gaps in [t0, t1] with no operation on device 0, each named by the
    benchmark's host span open at its middle ("loop" where none is)."""
    gaps, last = [], t0
    for s, e in union(trace["devices"][0]) + [(t1, t1)]:
        if s > last:
            gaps.append((last, min(s, t1)))
        last = max(last, e)
        if last >= t1:
            break
    host = trace["host"]
    named = []
    for a, b in gaps:
        mid = (a + b) / 2
        inside = [h for h in host if h[1] <= mid <= h[1] + h[2]]
        # the innermost span open at the gap's middle
        name = min(inside, key=lambda h: h[2])[0] if inside else "loop"
        named.append((name, b - a))
    return named


def within(events: list, spans: list) -> list:
    """The events whose start lies inside one of ``spans``."""
    ivs = sorted((s, s + d) for _, s, d in spans)
    out, j = [], 0
    for ev in sorted(events, key=lambda e: e[1]):
        while j < len(ivs) and ivs[j][1] < ev[1]:
            j += 1
        if j < len(ivs) and ivs[j][0] <= ev[1]:
            out.append(ev)
    return out


def op_seconds(ops: list) -> dict[str, float]:
    """Device seconds per operation, named by its HLO instruction (the
    profiler names an operation by its whole HLO text)."""
    out: dict[str, float] = {}
    for name, _, d in ops:
        name = name.split(" = ", 1)[0]
        out[name] = out.get(name, 0.0) + d
    return out


def breakdown(trace: dict, t0: float, t1: float, top: int = 10) -> dict:
    ops = [e for e in trace["devices"][0] if t0 <= e[1] <= t1]
    by_op = sorted(op_seconds(ops).items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(trace, t0, t1), key=lambda g: -g[1])[:top]
    return {"device_ops": [[n, s] for n, s in by_op],
            "idle_gaps": [[n, s] for n, s in gaps]}
