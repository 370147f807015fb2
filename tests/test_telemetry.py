"""repro.telemetry — the observability contracts (docs/observability.md):

* typed events with kind validation; wall-clock facts confined to the
  designated ``WALL_FIELDS`` and stripped by the canonical projection;
* a disabled recorder normalizes to no recorder at all (``active()``);
* two seeded churn runs produce **byte-identical** canonical event logs;
* the ``RunStore`` round-trips across a process restart, filters, and
  window-aggregates;
* the event log is a *sufficient statistic*: ``sim_aggregates`` rebuilds
  the in-memory ``SimReport`` totals exactly (the ISSUE acceptance gate);
* drift, kernel-profiling, and the real-hardware calibration loop all
  emit; the report CLI is exit-code gated.
"""

import json
import subprocess
import sys

import pytest

from repro.core import (EdgeSimulator, HiDPPlanner, Objective,
                        PlannerConfig, SimRequest, simulate)
from repro.core.edge_models import EDGE_MODELS, MODEL_DELTA, paper_cluster
from repro.fleet import ChurnTrace, FleetController
from repro.serving import PlanCache
from repro.telemetry import (KINDS, WALL_FIELDS, RunStore, TelemetryEvent,
                             TelemetryRecorder, active, sim_aggregates)
from repro.telemetry.report import generate, percentile, run_summary


# --------------------------------------------------------------------------
# events + recorder
# --------------------------------------------------------------------------

def test_event_schema_and_canonical_projection():
    e = TelemetryEvent(seq=3, kind="span", name="sim.request", value=1.5,
                       t=2.0, tenant="vgg19", epoch=1,
                       attrs={"retries": 1}, wall=123.4, wall_s=0.01)
    d = json.loads(e.to_json())
    assert d["wall"] == 123.4 and d["wall_s"] == 0.01
    c = json.loads(e.canonical())
    assert not any(f in c for f in WALL_FIELDS)
    # round-trip through JSON is lossless
    assert TelemetryEvent.from_json(e.to_json()) == e
    with pytest.raises(ValueError, match="unknown event kind"):
        TelemetryEvent(seq=0, kind="metric", name="x", value=1.0)
    assert set(KINDS) == {"span", "counter", "gauge"}


def test_recorder_seq_clock_and_counts():
    rec = TelemetryRecorder("r")
    rec.counter("a.hit", tenant="t1")
    rec.advance(5.0)
    rec.gauge("b.level", 3.0)
    rec.advance(2.0)                       # clock never goes backward
    rec.span("c.req", 1.25, t=4.0, epoch=2)
    assert [e.seq for e in rec.events] == [0, 1, 2]
    assert rec.events[0].t == 0.0 and rec.events[1].t == 5.0
    assert rec.clock == 5.0
    assert rec.events[2].value == 1.25 and rec.events[2].epoch == 2
    with rec.timed("d.pass", tenant="t1"):
        pass
    timed = rec.events[-1]
    assert timed.kind == "span" and timed.value == 0.0
    assert timed.wall_s is not None and timed.wall_s >= 0.0


def test_host_span_records_nested_spans_with_their_attributes():
    """host_span opens a profiler annotation always and, with a recorder,
    the recorder's wall-clocked trace context: nesting and attributes land
    in the log, and without a recorder nothing does."""
    from repro.telemetry import host_span, span_trees

    rec = TelemetryRecorder("spans")
    with host_span("outer", rec, wall_attrs={"queued_ms": 2.5}, tenant="t1",
                   request=1) as h:
        assert h.span_id is not None
        with host_span("inner", rec, plen=4):
            rec.counter("inner.hit")
    with host_span("alone", None, request=2):
        pass
    root, = span_trees(rec.events)
    assert root.name == "outer" and root.event.tenant == "t1"
    assert root.event.attrs == {"request": 1}      # no wall-clock reading
    inner, = root.children
    assert inner.name == "inner" and inner.event.attrs == {"plen": 4}
    assert [e.name for e in inner.events] == ["inner.hit"]
    assert all(n.event.wall_s is not None for n in root.walk())
    assert len(rec.events) == 3


def test_disabled_recorder_normalizes_away_and_emits_nothing():
    off = TelemetryRecorder("off", enabled=False)
    assert active(off) is None and active(None) is None
    assert active(TelemetryRecorder("on")) is not None
    off.counter("x")
    off.gauge("y", 1.0)
    assert off.events == []
    # instrumented classes accept a disabled recorder and drop it
    sim = EdgeSimulator(paper_cluster(), "hidp", telemetry=off)
    assert sim.telemetry is None


def test_recorder_flush_every_and_close(tmp_path):
    store = RunStore(tmp_path)
    rec = TelemetryRecorder("run-0001", store=store, flush_every=2)
    rec.counter("a")
    assert not store.events_path("run-0001").is_file()   # buffer below limit
    rec.counter("b")                                     # triggers flush
    assert len(store.events(rec.run)) == 2
    rec.gauge("c", 1.0)
    rec.close(extra="meta")
    assert len(store.events(rec.run)) == 3
    man = store.manifest(rec.run)
    assert man["events"] == 3 and man["extra"] == "meta"
    assert man["counts"] == {"span": 0, "counter": 2, "gauge": 1}
    with pytest.raises(ValueError):
        TelemetryRecorder("x", flush_every=0)
    with pytest.raises(ValueError):
        TelemetryRecorder("x", flush_every=2)            # no store to flush to


# --------------------------------------------------------------------------
# run store
# --------------------------------------------------------------------------

def _recorded_churn_run(root, seed_trace=None, planning_time="wall"):
    """One seeded churn run (crash + leave/join, SLOs, membership-keyed
    cache) recorded into a fresh run under ``root``.  Determinism tests
    pass ``planning_time=0.0`` — the documented seeded-replay mode that
    keeps wall-clock DP overhead out of simulated time."""
    cluster = paper_cluster()
    dag, delta = EDGE_MODELS["resnet152"](), MODEL_DELTA["resnet152"]
    trace = seed_trace or ChurnTrace.scripted([
        (0.35, "tx2", "crash"), (4.0, "nano", "leave"),
        (8.0, "tx2", "join"), (8.0, "nano", "join")])
    store = RunStore(root)
    rec = TelemetryRecorder(store.new_run("churn"), store=store)
    fleet = FleetController(cluster, trace, telemetry=rec)
    cache = PlanCache(HiDPPlanner(PlannerConfig(
        objective=Objective("energy", radio_power=4.0))), cluster,
        membership_source=fleet, telemetry=rec)
    sim = EdgeSimulator(cluster, "hidp", plan_cache=cache, fleet=fleet,
                        telemetry=rec, planning_time=planning_time)
    rep = sim.run([SimRequest(i, dag, 2.5 * i, delta, slo=2.0)
                   for i in range(5)])
    rec.close()
    return store, rec, rep, cache, fleet


def test_run_store_new_run_numbering_and_latest(tmp_path):
    store = RunStore(tmp_path)
    a, b = store.new_run("x"), store.new_run("x")
    assert (a, b) == ("x-0001", "x-0002")
    store.append(a, [TelemetryEvent(0, "counter", "n", 1.0)])
    store.write_manifest(b, {})
    assert store.runs() == [a, b]
    assert store.latest() == b                 # manifest created_unix wins
    assert RunStore(tmp_path / "empty").latest() is None


def test_run_store_restart_round_trip(tmp_path):
    store, rec, rep, cache, fleet = _recorded_churn_run(tmp_path)
    reopened = RunStore(tmp_path)              # a fresh process would do this
    assert reopened.runs() == store.runs()
    assert [e.to_json() for e in reopened.events(rec.run)] == \
        [e.to_json() for e in store.events(rec.run)]
    assert reopened.canonical_lines(rec.run) == store.canonical_lines(rec.run)
    assert reopened.manifest(rec.run)["events"] == len(rec.events)


def test_run_store_query_filters(tmp_path):
    store, rec, rep, cache, fleet = _recorded_churn_run(tmp_path)
    run = rec.run
    evs = store.events(run)
    assert [e.seq for e in evs] == sorted(e.seq for e in evs)
    # kind + name (exact and prefix-*)
    assert all(e.kind == "span" for e in store.events(run, kind="span"))
    hits = store.events(run, name="plan_cache.hit")
    assert len(hits) == cache.hits
    assert len(store.events(run, name="plan_cache.*")) >= \
        cache.hits + cache.misses
    # tenant + epoch + time range
    assert all(e.tenant == "resnet152"
               for e in store.events(run, tenant="resnet152"))
    ep1 = store.events(run, epoch=1)
    assert ep1 and all(e.epoch == 1 for e in ep1)
    windowed = store.events(run, t_range=(0.0, 2.5))
    assert windowed and all(0.0 <= e.t < 2.5 for e in windowed)


def test_run_store_windowed_aggregation(tmp_path):
    store = RunStore(tmp_path)
    rec = TelemetryRecorder(store.new_run("agg"), store=store)
    for i, v in enumerate((1.0, 2.0, 3.0, 4.0)):
        rec.counter("x", v, t=float(i))        # t = 0, 1, 2, 3
    rec.close()
    assert store.aggregate(rec.run, "x", window=2.0) == \
        [(0.0, 3.0), (2.0, 7.0)]
    assert store.aggregate(rec.run, "x", window=2.0, reduce="count") == \
        [(0.0, 2.0), (2.0, 2.0)]
    assert store.aggregate(rec.run, "x", window=4.0, reduce="mean") == \
        [(0.0, 2.5)]
    assert store.aggregate(rec.run, "x", window=1.0, reduce="max")[-1] == \
        (3.0, 4.0)
    with pytest.raises(ValueError, match="window"):
        store.aggregate(rec.run, "x", window=0.0)
    with pytest.raises(ValueError, match="reducer"):
        store.aggregate(rec.run, "x", reduce="median")


# --------------------------------------------------------------------------
# determinism — the headline contract
# --------------------------------------------------------------------------

def test_two_seeded_runs_are_byte_identical_modulo_wall(tmp_path):
    """Seeded replay determinism: the canonical (wall-stripped) event logs
    of two identical churn runs are byte-identical.  ``planning_time=0.0``
    is the replay mode — with wall-clock DP overhead charged into domain
    time (the default), completion times inherit timer jitter."""
    s0, r0, *_ = _recorded_churn_run(tmp_path / "a", planning_time=0.0)
    s1, r1, *_ = _recorded_churn_run(tmp_path / "b", planning_time=0.0)
    l0, l1 = s0.canonical_lines(r0.run), s1.canonical_lines(r1.run)
    assert l0 and l0 == l1
    # and the raw logs differ ONLY in the designated wall fields
    for e0, e1 in zip(s0.events(r0.run), s1.events(r1.run)):
        d0, d1 = e0.to_dict(), e1.to_dict()
        for f in WALL_FIELDS:
            d0.pop(f, None), d1.pop(f, None)
        assert d0 == d1


def test_poisson_churn_run_deterministic_under_seed(tmp_path):
    names = [n.name for n in paper_cluster().nodes]
    t0 = ChurnTrace.poisson(names, rate=0.3, horizon=20.0, seed=11)
    t1 = ChurnTrace.poisson(names, rate=0.3, horizon=20.0, seed=11)
    s0, r0, *_ = _recorded_churn_run(tmp_path / "a", seed_trace=t0,
                                     planning_time=0.0)
    s1, r1, *_ = _recorded_churn_run(tmp_path / "b", seed_trace=t1,
                                     planning_time=0.0)
    assert s0.canonical_lines(r0.run) == s1.canonical_lines(r1.run)


# --------------------------------------------------------------------------
# reconstruction — the ISSUE acceptance gate
# --------------------------------------------------------------------------

def test_log_reconstructs_sim_report_aggregates_exactly(tmp_path):
    """The durable event log is a sufficient statistic for the run: the
    report's ``sim_aggregates`` equals the in-memory ``SimReport`` totals
    — retries, migrations, SLO violations, joules, per-tenant cache
    hits/misses — exactly, not approximately."""
    store, rec, rep, cache, fleet = _recorded_churn_run(tmp_path)
    agg = sim_aggregates(store, rec.run)
    assert agg["requests"] == len(rep.records) == 5
    assert agg["total_retries"] == rep.total_retries() == 1
    assert agg["total_migrations"] == rep.total_migrations()
    assert agg["slo_violations"] == rep.slo_violations()
    assert agg["total_active_joules"] == \
        sum(r.active_energy for r in rep.records)
    assert sum(agg["cache_hits_by_tenant"].values()) == cache.hits
    assert sum(agg["cache_misses_by_tenant"].values()) == cache.misses
    assert agg["cache_hits_by_tenant"] == {"resnet152": cache.hits}
    # per-request latencies reconstruct too
    assert agg["latencies"] == [r.latency for r in rep.records]
    # the crash's retry lands in the epoch that crash created
    assert sum(agg["retries_by_epoch"].values()) == rep.total_retries()
    # fleet history: one membership gauge per epoch, stamped at epoch time
    gauges = store.events(rec.run, kind="gauge", name="fleet.membership")
    assert [(e.epoch, e.t) for e in gauges] == \
        [(ep.epoch, ep.time) for ep in fleet.epochs[1:]]
    # frontier passes carry wall timings; their count equals cache misses
    passes = store.events(rec.run, kind="span", name="plan.frontier_pass")
    assert len(passes) == cache.misses
    assert all(p.wall_s is not None and p.wall_s > 0 for p in passes)


def test_run_summary_and_report_render(tmp_path):
    store, rec, rep, cache, fleet = _recorded_churn_run(tmp_path)
    summary = run_summary(store, rec.run)
    lats = sorted(r.latency for r in rep.records)
    assert summary["p50_latency_s"] == percentile([r.latency
                                                   for r in rep.records], 50)
    assert lats[0] <= summary["p50_latency_s"] <= lats[-1]
    assert summary["cache_hit_rate"] == pytest.approx(
        cache.hits / (cache.hits + cache.misses))
    assert summary["epochs"] == fleet.epoch
    text = generate(store, rec.run)
    assert f"run {rec.run}" in text and "tenant resnet152" in text


def test_report_cli_exit_codes(tmp_path):
    store, rec, *_ = _recorded_churn_run(tmp_path / "full")
    env_root = str(tmp_path / "full")
    ok = subprocess.run(
        [sys.executable, "-m", "repro.telemetry.report", env_root],
        capture_output=True, text=True)
    assert ok.returncode == 0 and f"run {rec.run}" in ok.stdout
    empty = subprocess.run(
        [sys.executable, "-m", "repro.telemetry.report",
         str(tmp_path / "nothing")],
        capture_output=True, text=True)
    assert empty.returncode == 1 and "failed" in empty.stderr
    with pytest.raises(ValueError):
        generate(RunStore(tmp_path / "still-nothing"))


# --------------------------------------------------------------------------
# the other instrumented layers
# --------------------------------------------------------------------------

def test_feedback_drift_emits_gauge():
    from repro.profiling import FeedbackLoop, LearnedCostModel, Sample

    model = LearnedCostModel.fit(
        [Sample("n/gpu", "conv", w, 0.0, w / 1e9)
         for w in (1e8, 2e8, 4e8, 8e8)])
    rec = TelemetryRecorder("drift")
    fb = FeedbackLoop(model, threshold=0.3, telemetry=rec,
                      calibration_version=7)
    for i in range(40):
        work = 1e8 * (1 + i % 5)
        fb.observe("n/gpu", "conv", work, 0.0, 3.0 * work / 1e9)
    assert fb.replans == 1
    drifts = [e for e in rec.events if e.name == "feedback.drift"]
    assert len(drifts) == 1
    (d,) = drifts
    assert d.kind == "gauge" and d.value == fb.events[0].mean_error > 0.3
    assert d.attrs["metric"] == "latency"
    assert d.attrs["resource"] == "n/gpu"
    assert d.attrs["calibration_version"] == 8   # bumped at the trip


def test_kernel_sweep_and_calibration_loop_emit(tmp_path):
    import jax

    from repro.profiling import (CalibrationStore, Profiler,
                                 calibrate_kernels)

    store = RunStore(tmp_path / "telemetry")
    rec = TelemetryRecorder(store.new_run("calib"), store=store)
    calib = CalibrationStore(tmp_path / "calibrations")
    cluster = paper_cluster()
    prof = Profiler(warmup=0, repeats=1, trim=0)
    # pin devices: earlier tests may have initialized jax with a forced
    # host device count, and the default sweeps every visible device
    model, version = calibrate_kernels(
        calib, cluster, profiler=prof, telemetry=rec,
        devices=jax.devices()[:1],
        shapes={"attn": ((1, 32, 2, 16),), "decode": ((1, 32, 2, 16),),
                "ssd": ((1, 32, 2, 16, 8),)})
    rec.close()
    assert version == 1
    # persisted through the CalibrationStore and loadable again
    assert set(calib.load(cluster).entries) == set(model.entries)
    spans = store.events(rec.run, kind="span", name="profile.kernel")
    assert {e.attrs["kind"] for e in spans} == {"attn", "decode", "ssd"}
    assert all(e.wall_s is not None and e.wall_s > 0 for e in spans)
    done = store.events(rec.run, name="profile.calibration")
    assert len(done) == 1 and done[0].attrs["version"] == 1
    assert done[0].attrs["samples"] == len(spans) == 3


def test_engine_submit_and_replan_counters():
    """ServingEngine cache-resolution counters (the membership/drift
    re-plan paths are covered end-to-end in test_serving): a submit with
    no plan cache records resolution='none'."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import ServingEngine

    cfg = get_config("gemma-2b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    rec = TelemetryRecorder("engine")
    eng = ServingEngine(model, params, max_batch=1, max_len=16,
                        telemetry=rec)
    eng.submit(np.asarray([1, 2], np.int32), max_new_tokens=1)
    subs = [e for e in rec.events if e.name == "engine.submit"]
    assert len(subs) == 1 and subs[0].attrs["resolved"] == "none"


# --------------------------------------------------------------------------
# satellite: SimReport empty-report guards
# --------------------------------------------------------------------------

def test_sim_report_empty_guards():
    sim = EdgeSimulator(paper_cluster(), "hidp")
    rep = sim.run([])
    assert rep.records == []
    assert rep.predicted_energies() == {}
    assert rep.prediction_error() == {}
    # simulate() with an empty workload goes through the same guards
    rep2 = simulate(paper_cluster(), "hidp", [])
    assert rep2.prediction_error() == {}
