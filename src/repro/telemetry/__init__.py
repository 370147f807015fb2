"""repro.telemetry — structured fleet telemetry and the queryable run store.

The "observe" leg of the paper's closed loop, made durable: every
subsystem that matters at run time — the :class:`~repro.core.simulator.
EdgeSimulator` (request/attempt spans, retries, migrations, SLO,
joules), the :class:`~repro.serving.plan_cache.PlanCache` (per-tenant
hits/misses/evictions, DP frontier-pass spans), the
:class:`~repro.serving.engine.ServingEngine` (per-tenant cache
resolutions, EXPLORE re-entries, the spans of every step), the :class:`~repro.fleet.
FleetController` (membership gauges, leader fail-overs), the
:class:`~repro.profiling.FeedbackLoop` (drift magnitude gauges), the
:class:`~repro.runtime.elastic.ElasticController` (world-size gauges),
and the :class:`~repro.profiling.Profiler` (kernel-profile spans) —
takes an optional ``telemetry=`` :class:`TelemetryRecorder` and emits
typed, timestamped events into it.

Events land in a :class:`RunStore` (JSONL log + atomic manifest, one
directory per run — the same filing idiom as ``CalibrationStore``) with
filtering and windowed-aggregation queries; :mod:`repro.telemetry.report`
turns a run into a p50/p99/energy/hit-rate summary and reconstructs the
simulator's ``SimReport`` aggregates *exactly* from the log.

Determinism and overhead are contracts, not hopes: seeded replays are
byte-identical modulo the designated wall-clock fields, and a disabled
recorder normalizes to no recorder at all (see :func:`active`), gated at
≤2 % in fig7.  :func:`host_span` also puts a span in the profiler's own
trace, beside the device's work.  See docs/observability.md.
"""

from .events import KINDS, WALL_FIELDS, TelemetryEvent  # noqa: F401
from .recorder import (SpanHandle, TelemetryRecorder, active,  # noqa: F401
                       host_span)
from .report import run_summary, sim_aggregates  # noqa: F401
from .store import RunStore  # noqa: F401
from .trace import (SpanNode, critical_path,  # noqa: F401
                    node_utilization, overlap_headroom,
                    request_critical_paths, span_trees, tree_lines)
