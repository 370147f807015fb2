"""Profiler — the measurement half of the paper's DNN Model Analyzer.

Two measurement paths:

* ``profile_cluster`` micro-benchmarks the analytic block DAGs from
  ``core/edge_models.py`` against a ground truth — by default the datasheet
  itself, or a ``SyntheticGroundTruth`` whose per-processor rates diverge
  from it (thermal throttling, contention, a mis-declared board).  This is
  the deterministic testbed path: seeded jitter, warmup discards, trimmed
  means — the shape of real profiling without real hardware.

* ``profile_kernels`` wall-clock times the actual jax kernels in
  ``repro.kernels`` — the FULL set (prefill flash attention, decode
  attention, Mamba-2 SSD; Pallas on TPU, the blocked jnp path elsewhere) —
  per device, with per-kind shape sweeps (``DEFAULT_KERNEL_SHAPES``).
  ``repro.profiling.calibrate_kernels`` loops it over every visible jax
  device, fits a ``LearnedCostModel`` and persists it through the
  ``CalibrationStore`` — the real-hardware calibration loop.

Both produce ``learned.Sample`` rows that ``LearnedCostModel.fit`` consumes.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Mapping, Sequence

import numpy as np

from repro.core.cost_model import Cluster, Node, Processor
from repro.core.dag import Block, ModelDAG

from .learned import Sample


def block_traffic(block: Block) -> float:
    """Bytes a block touches: weights plus in/out activations."""
    return block.param_bytes + block.bytes_in + block.bytes_out


# --------------------------------------------------------------------------
# Ground truth — what the hardware actually does
# --------------------------------------------------------------------------

@dataclasses.dataclass
class SyntheticGroundTruth:
    """True per-processor performance, possibly diverging from the datasheet.

    ``rate_scale`` maps ``(node_name, proc_name)`` (or ``node_name`` for the
    whole node) to a multiplier on the analytic rate: 0.4 means the processor
    sustains 40% of what the cost model believes.  ``power_scale`` does the
    same for active power draw: 1.5 means the processor really burns 1.5× its
    datasheet active watts (DVFS residency, rail losses, a mis-declared TDP)
    — the divergence the energy predictors exist to learn.  ``mem_bw`` and
    ``overhead_s`` add the memory-traffic and fixed-launch terms real
    measurements contain; ``noise`` is the relative jitter σ applied by
    ``sample_seconds`` (deterministic under a caller-provided rng).
    """

    cluster: Cluster
    rate_scale: Mapping[str, float] | Mapping[tuple[str, str], float] = \
        dataclasses.field(default_factory=dict)
    power_scale: Mapping[str, float] | Mapping[tuple[str, str], float] = \
        dataclasses.field(default_factory=dict)
    mem_bw: float = 12e9
    overhead_s: float = 2e-4
    noise: float = 0.0

    def _proc(self, node_name: str, proc_name: str) -> tuple[Node, Processor]:
        for n in self.cluster.nodes:
            if n.name == node_name:
                for p in n.processors:
                    if p.name == proc_name:
                        return n, p
        raise KeyError(f"{node_name}/{proc_name}")

    @staticmethod
    def _scale_from(table: Mapping, node_name: str, proc_name: str) -> float:
        rs = dict(table)
        return rs.get((node_name, proc_name),
                      rs.get(f"{node_name}/{proc_name}",
                             rs.get(node_name, 1.0)))

    def scale(self, node_name: str, proc_name: str) -> float:
        return self._scale_from(self.rate_scale, node_name, proc_name)

    def active_watts(self, node_name: str, proc_name: str) -> float:
        """The active power the hardware actually draws (W)."""
        _, p = self._proc(node_name, proc_name)
        return p.active_power * self._scale_from(self.power_scale,
                                                 node_name, proc_name)

    def rate(self, node_name: str, proc_name: str, kind: str,
             delta: float) -> float:
        """The rate the hardware actually sustains (flops/s at this δ)."""
        _, p = self._proc(node_name, proc_name)
        return p.rate(delta, kind) * self.scale(node_name, proc_name)

    def compute_seconds(self, node_name: str, proc_name: str, flops: float,
                        kind: str, delta: float) -> float:
        """Pure compute time of a shard — what the simulator's EXECUTE
        state charges when this ground truth replaces the datasheet."""
        return flops / max(self.rate(node_name, proc_name, kind, delta),
                           1e-12)

    def block_seconds(self, node_name: str, proc_name: str, block: Block,
                      delta: float) -> float:
        """Noise-free micro-benchmark latency of one block."""
        return (self.compute_seconds(node_name, proc_name, block.flops,
                                     block.kind, delta)
                + block_traffic(block) / self.mem_bw
                + self.overhead_s)

    def sample_seconds(self, node_name: str, proc_name: str, block: Block,
                       delta: float, rng: np.random.Generator) -> float:
        base = self.block_seconds(node_name, proc_name, block, delta)
        if self.noise <= 0:
            return base
        return base * float(np.clip(1.0 + self.noise * rng.standard_normal(),
                                    0.5, 2.0))


# --------------------------------------------------------------------------
# Profiler
# --------------------------------------------------------------------------

# Default shape sweep for the real-kernel path, per kernel kind.  Small by
# design (CI runs these through the blocked jnp path on CPU); a hardware
# deployment passes its own per-device shapes to ``profile_kernels``.
DEFAULT_KERNEL_SHAPES: dict[str, tuple[tuple[int, ...], ...]] = {
    # (B, T, H, D) — prefill flash attention
    "attn": ((1, 64, 4, 32), (1, 128, 4, 32), (2, 128, 4, 32)),
    # (B, S, H, D) — one decode token against an S-long KV cache
    "decode": ((1, 128, 4, 32), (2, 128, 4, 32), (2, 256, 4, 32)),
    # (B, T, NH, HD, N) — Mamba-2 chunked SSD scan
    "ssd": ((1, 64, 4, 32, 16), (1, 128, 4, 32, 16), (2, 128, 4, 32, 16)),
}


@dataclasses.dataclass
class Profiler:
    """Micro-benchmark driver: warmup, repeats, trimmed mean, fixed seed."""

    warmup: int = 2
    repeats: int = 5
    trim: int = 1                # drop the k fastest and k slowest repeats
    seed: int = 0

    def _trimmed_mean(self, xs: Sequence[float]) -> float:
        xs = sorted(xs)
        if len(xs) > 2 * self.trim:
            xs = xs[self.trim:len(xs) - self.trim]
        return float(np.mean(xs))

    def profile_cluster(self, cluster: Cluster,
                        dags: Mapping[str, ModelDAG],
                        deltas: Mapping[str, float],
                        ground_truth: SyntheticGroundTruth | None = None,
                        ) -> list[Sample]:
        """Per-(block × processor) timing/energy samples over every node.

        Deterministic: one seeded generator drives all jitter, and the
        iteration order is fixed (nodes → processors → dags → blocks).
        """
        gt = ground_truth or SyntheticGroundTruth(cluster)
        rng = np.random.default_rng(self.seed)
        samples: list[Sample] = []
        for node in cluster.nodes:
            for proc in node.processors:
                for name, dag in dags.items():
                    delta = deltas[name]
                    for block in dag.blocks:
                        for _ in range(self.warmup):   # cache/DVFS settle
                            gt.sample_seconds(node.name, proc.name, block,
                                              delta, rng)
                        reps = [gt.sample_seconds(node.name, proc.name,
                                                  block, delta, rng)
                                for _ in range(self.repeats)]
                        lat = self._trimmed_mean(reps)
                        samples.append(Sample(
                            key=f"{node.name}/{proc.name}",
                            kind=block.kind,
                            work=block.flops * delta,
                            traffic=block_traffic(block),
                            latency_s=lat,
                            energy_j=lat * gt.active_watts(node.name,
                                                           proc.name)))
        return samples

    # ------------------------------------------------------- real kernels
    def profile_kernels(self, *, kinds: Sequence[str] | None = None,
                        shapes: Mapping[str, Sequence[tuple[int, ...]]]
                        | None = None,
                        block_q: int = 32, block_k: int = 32,
                        device=None, key: str | None = None,
                        telemetry=None) -> list[Sample]:
        """Wall-clock the FULL repro.kernels set on one device: prefill
        flash attention, single-token decode attention against a KV cache,
        and the Mamba-2 chunked SSD scan.

        ``shapes`` maps kernel kind → shape tuples (see
        ``DEFAULT_KERNEL_SHAPES`` for the per-kind layout); ``kinds``
        restricts the sweep.  ``device`` (a ``jax.Device``) places every
        input there before timing — the per-device path a hardware
        deployment loops over — and ``key`` overrides the Sample key
        (default ``host/<backend>`` for the host, ``<platform>:<id>`` for
        an explicit device).  With ``telemetry`` each measured point also
        lands as a ``profile.kernel`` span whose wall_s is the trimmed-mean
        latency.  Same discipline as the synthetic path throughout: warmup
        → repeats → trimmed mean, seeded inputs.
        """
        import jax
        import jax.numpy as jnp

        from repro.kernels import ops
        from repro.telemetry import active as _tel_active

        tel = _tel_active(telemetry)
        if key is None:
            key = (f"host/{jax.default_backend()}" if device is None
                   else f"{device.platform}:{device.id}")
        table = dict(DEFAULT_KERNEL_SHAPES)
        if shapes:
            table.update(shapes)
        sweep = tuple(kinds) if kinds is not None else tuple(table)
        unknown = [k for k in sweep if k not in table]
        if unknown:
            raise KeyError(f"unknown kernel kinds {unknown}; "
                           f"known: {sorted(table)}")
        samples: list[Sample] = []
        rng = jax.random.PRNGKey(self.seed)

        def put(x):
            return jax.device_put(x, device) if device is not None else x

        def bench(fn, *args) -> float:
            args = tuple(put(a) for a in args)
            for _ in range(self.warmup):
                jax.block_until_ready(fn(*args))
            reps = []
            for _ in range(self.repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*args))
                reps.append(time.perf_counter() - t0)
            return self._trimmed_mean(reps)

        def record(kind: str, shape: tuple[int, ...], flops: float,
                   traffic: float, lat: float) -> None:
            samples.append(Sample(key=key, kind=kind, work=flops,
                                  traffic=traffic, latency_s=lat))
            if tel is not None:
                tel.span("profile.kernel", 0.0, wall_s=lat, kind=kind,
                         key=key, shape="x".join(map(str, shape)),
                         flops=flops)

        for kind in sweep:
            for shape in table[kind]:
                if kind == "attn":
                    b, t, h, d = shape
                    ks = jax.random.split(rng, 3)
                    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
                    k = jax.random.normal(ks[1], (b, t, h, d), jnp.float32)
                    v = jax.random.normal(ks[2], (b, t, h, d), jnp.float32)
                    lat = bench(lambda q, k, v: ops.flash_attention(
                        q, k, v, block_q=block_q, block_k=block_k), q, k, v)
                    flops = 4.0 * b * t * t * h * d       # QK^T + AV
                    traffic = 4.0 * (q.size + k.size + v.size + q.size)
                elif kind == "decode":
                    b, s, h, d = shape
                    ks = jax.random.split(rng, 3)
                    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
                    kc = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
                    vc = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)
                    lengths = jnp.full((b,), s, jnp.int32)
                    lat = bench(lambda q, kc, vc, ln: ops.decode_attention(
                        q, kc, vc, ln, block_k=block_k), q, kc, vc, lengths)
                    flops = 4.0 * b * s * h * d           # qK^T + aV
                    traffic = 4.0 * (q.size + kc.size + vc.size + q.size)
                else:                                     # ssd
                    b, t, nh, hd, n = shape
                    ks = jax.random.split(rng, 4)
                    x = jax.random.normal(ks[0], (b, t, nh, hd), jnp.float32)
                    dt = jax.random.uniform(ks[1], (b, t, nh), jnp.float32,
                                            0.001, 0.1)
                    A = -jnp.ones((nh,), jnp.float32)
                    B = jax.random.normal(ks[2], (b, t, n), jnp.float32)
                    C = jax.random.normal(ks[3], (b, t, n), jnp.float32)
                    D = jnp.ones((nh,), jnp.float32)
                    lat = bench(lambda x, dt, B, C: ops.ssd(
                        x, dt, A, B, C, D, chunk=min(64, t)), x, dt, B, C)
                    flops = 6.0 * b * t * nh * hd * n     # in/out proj + scan
                    traffic = 4.0 * (x.size + B.size + C.size + x.size)
                record(kind, shape, flops, traffic, lat)
        return samples
