"""Serving engine: continuous batching, slot reuse, and greedy-decode
equivalence against a reference incremental loop."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serving.engine import ServingEngine
from repro.telemetry import TelemetryRecorder


@pytest.fixture(scope="module")
def small_lm():
    cfg = get_config("gemma-2b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    return cfg, model, params


def _replans_recorded(rec: TelemetryRecorder) -> int:
    """EXPLORE re-entries the engine reported to its recorder."""
    return sum(e.name == "engine.replan" for e in rec.events)


def _reference_greedy(model, params, prompt, n_new):
    """Full-forward greedy decoding (no cache) — the exactness oracle."""
    toks = list(map(int, prompt))
    for _ in range(n_new):
        logits = model.apply_train(
            params, {"tokens": jnp.asarray([toks], jnp.int32)}, remat=False)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def test_engine_single_request_matches_reference(small_lm):
    cfg, model, params = small_lm
    prompt = np.asarray([5, 9, 2, 7], np.int32)
    want = _reference_greedy(model, params, prompt, 6)

    eng = ServingEngine(model, params, max_batch=2, max_len=32)
    rid = eng.submit(prompt, max_new_tokens=6)
    done = eng.run_until_done()
    got = done[rid].generated[:6]
    assert got == want, (got, want)


def test_engine_batches_multiple_requests(small_lm):
    cfg, model, params = small_lm
    eng = ServingEngine(model, params, max_batch=2, max_len=32)
    prompts = [np.asarray(p, np.int32) for p in
               ([1, 2, 3], [9, 8, 7, 6], [4, 4], [11, 3, 5, 2, 1])]
    wants = [_reference_greedy(model, params, p, 4) for p in prompts]
    rids = [eng.submit(p, max_new_tokens=4) for p in prompts]
    done = eng.run_until_done()
    assert len(done) == 4                      # queue drained via slot reuse
    for rid, want in zip(rids, wants):
        assert done[rid].generated[:4] == want


def test_engine_respects_max_len(small_lm):
    cfg, model, params = small_lm
    eng = ServingEngine(model, params, max_batch=1, max_len=12)
    rid = eng.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=100)
    done = eng.run_until_done()
    assert done[rid].done
    assert 3 + len(done[rid].generated) <= 12 + 1


def test_engine_ssm_family():
    """Recurrent-state arch (mamba2) through the same engine path."""
    cfg = get_config("mamba2-780m").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(5))
    prompt = np.asarray([3, 1, 4], np.int32)
    want = _reference_greedy(model, params, prompt, 5)
    eng = ServingEngine(model, params, max_batch=2, max_len=24)
    rid = eng.submit(prompt, max_new_tokens=5)
    done = eng.run_until_done()
    assert done[rid].generated[:5] == want


def test_engine_feedback_reenters_explore_on_drift(small_lm):
    """Closed loop at serving time: a cost model that wildly underestimates
    decode latency drifts immediately; the engine re-enters EXPLORE (Fig. 4)
    and fires the re-plan hook, and the refitted model then tracks reality."""
    from repro.profiling import FeedbackLoop, LearnedCostModel

    cfg, model, params = small_lm
    beliefs = LearnedCostModel()
    # believes a decode step takes ~1 ns — off by many orders of magnitude
    beliefs.fit_entry("engine/decode", "decode",
                      [(1.0, 0.0, 1e-9), (2.0, 0.0, 2e-9)])
    replans = []
    fb = FeedbackLoop(beliefs, threshold=0.75,
                      on_drift=lambda: replans.append(fb.observations))
    rec = TelemetryRecorder("engine")
    eng = ServingEngine(model, params, max_batch=1, max_len=64,
                        feedback=fb, on_replan=lambda: None, telemetry=rec)
    rid = eng.submit(np.asarray([5, 9, 2], np.int32), max_new_tokens=40)
    done = eng.run_until_done()
    assert done[rid].done
    assert eng.replans >= 1 and replans
    assert _replans_recorded(rec) == eng.replans
    # after the hard refit the model's belief is in the measured ballpark
    pred = beliefs.predict("engine/decode", "decode", 1.0, 0.0)
    assert pred is not None and pred > 1e-7


def test_dominant_objective_tie_break_is_deterministic(small_lm):
    """Ties resolve by the fixed METRICS order (latency > energy > edp),
    never by arrival or dict order — cache keys and re-plan objectives must
    be reproducible across runs."""
    cfg, model, params = small_lm
    eng = ServingEngine(model, params, max_batch=2, max_len=32)
    # 1 edp vs 1 energy (latency 0): energy wins — METRICS order
    eng.submit(np.asarray([1], np.int32), max_new_tokens=2, objective="edp")
    eng.submit(np.asarray([2], np.int32), max_new_tokens=2,
               objective="energy")
    assert eng.dominant_objective() == "energy"
    # 1 latency / 1 energy / 1 edp: latency wins the three-way tie
    eng.submit(np.asarray([3], np.int32), max_new_tokens=2,
               objective="latency")
    assert eng.dominant_objective() == "latency"
    # a clear majority still wins regardless of order
    eng.submit(np.asarray([4], np.int32), max_new_tokens=2, objective="edp")
    eng.submit(np.asarray([5], np.int32), max_new_tokens=2, objective="edp")
    assert eng.dominant_objective() == "edp"


def _toy_cache():
    """A PlanCache over the paper cluster for a small synthetic workload."""
    from repro.core import (Block, HiDPPlanner, ModelDAG, Objective,
                            PlannerConfig)
    from repro.core.edge_models import battery_cluster
    from repro.serving import PlanCache

    blocks = tuple(Block(name=f"b{i}", flops=2e9, param_bytes=1e6,
                         bytes_in=4e5, bytes_out=4e5, kind="conv")
                   for i in range(6))
    dag = ModelDAG(name="toy", blocks=blocks, input_bytes=4e5,
                   output_bytes=4e5)
    cluster = battery_cluster()
    planner = HiDPPlanner(PlannerConfig(
        objective=Objective("energy", radio_power=4.0)))
    return PlanCache(planner, cluster), dag


def test_engine_submit_resolves_objectives_from_plan_cache(small_lm):
    """Mixed-objective traffic is served from one cached frontier: the
    first submit pays the DP pass, every later submit is a hit."""
    cfg, model, params = small_lm
    cache, dag = _toy_cache()
    eng = ServingEngine(model, params, max_batch=2, max_len=32,
                        plan_cache=cache, default_dag=dag)
    from repro.core import Objective

    objectives = ("latency", "energy", "edp", "energy")
    for i, obj in enumerate(objectives):
        eng.submit(np.asarray([i + 1, 2], np.int32), max_new_tokens=2,
                   objective=obj)
    assert cache.misses == 1 and cache.hits == len(objectives) - 1
    # the engine's current plan is the last request's selection off the front
    want = cache.front(dag).select(Objective("energy"))
    assert eng.plan.global_plan.partition == want.global_plan.partition
    done = eng.run_until_done()
    assert len(done) == len(objectives)
    assert cache.misses == 1                    # execution never re-plans


def test_engine_drift_triggers_exactly_one_cache_replan(small_lm):
    """Drift while serving: the calibration version bumps, the cached
    frontier invalidates, and the engine re-enters EXPLORE with exactly one
    frontier re-plan at the dominant objective."""
    from repro.profiling import FeedbackLoop, LearnedCostModel

    cfg, model, params = small_lm
    cache, dag = _toy_cache()
    beliefs = LearnedCostModel()
    beliefs.fit_entry("engine/decode", "decode",
                      [(1.0, 0.0, 1e-9), (2.0, 0.0, 2e-9)])
    fb = FeedbackLoop(beliefs, threshold=0.75)
    rec = TelemetryRecorder("engine")
    eng = ServingEngine(model, params, max_batch=1, max_len=64,
                        feedback=fb, plan_cache=cache, default_dag=dag,
                        telemetry=rec)
    rid = eng.submit(np.asarray([5, 9, 2], np.int32), max_new_tokens=40,
                     objective="energy")
    done = eng.run_until_done()
    assert done[rid].done
    assert eng.replans >= 1 and _replans_recorded(rec) == eng.replans
    # one miss to warm the cache + one EXPLORE re-plan per drift event
    assert cache.misses == 1 + eng.replans
    assert cache.invalidations == eng.replans
    assert cache.version == eng.replans


def test_engine_drift_replans_each_tenant_exactly_once(small_lm):
    """Two tenants share one cache; a drift event re-enters EXPLORE with
    exactly one frontier re-plan *per in-flight tenant*, each at that
    tenant's own dominant objective."""
    import dataclasses

    from repro.core import dag_fingerprint
    from repro.profiling import FeedbackLoop, LearnedCostModel

    cfg, model, params = small_lm
    cache, dag_a = _toy_cache()
    dag_b = dataclasses.replace(dag_a, name="toy_b",
                                blocks=dag_a.blocks[:-1])
    beliefs = LearnedCostModel()
    beliefs.fit_entry("engine/decode", "decode",
                      [(1.0, 0.0, 1e-9), (2.0, 0.0, 2e-9)])
    fb = FeedbackLoop(beliefs, threshold=0.75)
    rec = TelemetryRecorder("engine")
    eng = ServingEngine(model, params, max_batch=2, max_len=64,
                        feedback=fb, plan_cache=cache, telemetry=rec)
    ra = eng.submit(np.asarray([5, 9, 2], np.int32), max_new_tokens=40,
                    objective="energy", dag=dag_a)
    rb = eng.submit(np.asarray([1, 4], np.int32), max_new_tokens=40,
                    objective="latency", dag=dag_b)
    done = eng.run_until_done()
    assert done[ra].done and done[rb].done
    assert eng.replans >= 1 and _replans_recorded(rec) == eng.replans
    # one miss per tenant to warm the cache + one re-plan per tenant per
    # drift event — never more
    assert cache.misses == 2 + 2 * eng.replans
    assert cache.invalidations == eng.replans
    # each tenant's latest selection is tracked separately
    assert set(eng.tenant_plans) == {dag_fingerprint(dag_a),
                                     dag_fingerprint(dag_b)}
    assert eng.tenant_plans[dag_fingerprint(dag_a)].dag_name == "toy"
    assert eng.tenant_plans[dag_fingerprint(dag_b)].dag_name == "toy_b"


def test_engine_membership_epoch_replans_each_tenant_once(small_lm):
    """The churn path (docs/fleet.md): a FleetController membership epoch
    re-enters EXPLORE with exactly one plan resolution per in-flight
    tenant — a single frontier pass for the never-seen membership, and
    zero DP work when the departed node returns (the membership key flips
    back to its original value)."""
    from repro.fleet import ChurnTrace, FleetController

    cfg, model, params = small_lm
    cache, dag = _toy_cache()
    fleet = FleetController(cache.cluster, ChurnTrace.scripted(
        [(1.0, "tx2", "leave"), (2.0, "tx2", "join")]))
    cache.membership_source = fleet
    rec = TelemetryRecorder("engine")
    eng = ServingEngine(model, params, max_batch=2, max_len=32,
                        plan_cache=cache, default_dag=dag, telemetry=rec)
    fleet.on_epoch = lambda ep: eng.on_membership_change(ep)
    eng.submit(np.asarray([1, 2], np.int32), max_new_tokens=4)
    assert cache.misses == 1                 # cold pass, full membership
    fleet.advance(1.5)                       # tx2 leaves → epoch 1
    assert eng.replans == 1 and _replans_recorded(rec) == 1
    assert cache.misses == 2                 # one pass for the new mask
    assert all(a.node.name != "tx2"
               for a in eng.plan.global_plan.assignments)
    fleet.advance(2.5)                       # tx2 returns → epoch 2
    assert eng.replans == 2 and _replans_recorded(rec) == 2
    assert cache.misses == 2                 # warm return: zero DP work
    assert cache.hits >= 1
    done = eng.run_until_done()
    assert len(done) == 1


def test_engine_submit_requires_tenant_when_cache_wired(small_lm):
    """A plan_cache without a tenant (no dag= and no default_dag) cannot
    resolve a plan; naming a dag without a cache is equally a wiring
    error."""
    cfg, model, params = small_lm
    cache, dag = _toy_cache()
    eng = ServingEngine(model, params, max_batch=1, max_len=32,
                        plan_cache=cache)
    with pytest.raises(ValueError, match="tenant"):
        eng.submit(np.asarray([1], np.int32), max_new_tokens=2)
    eng.submit(np.asarray([1], np.int32), max_new_tokens=2, dag=dag)
    assert cache.misses == 1
    plain = ServingEngine(model, params, max_batch=1, max_len=32)
    with pytest.raises(ValueError, match="plan_cache"):
        plain.submit(np.asarray([1], np.int32), max_new_tokens=2, dag=dag)
    with pytest.raises(ValueError, match="plan_cache"):
        ServingEngine(model, params, default_dag=dag)


def test_engine_submit_delta_is_part_of_the_cache_key(small_lm):
    """δ rides the cache key: a submit at the delta that warmed the front
    hits; a different delta is a different tenant entry (one more pass)."""
    cfg, model, params = small_lm
    cache, dag = _toy_cache()
    cache.front(dag, 70.0)                         # warmed at δ=70
    eng = ServingEngine(model, params, max_batch=2, max_len=32,
                        plan_cache=cache, default_dag=dag)
    eng.submit(np.asarray([1, 2], np.int32), max_new_tokens=2, delta=70.0)
    assert (cache.misses, cache.hits) == (1, 1)    # warm front reused
    eng.submit(np.asarray([3], np.int32), max_new_tokens=2, delta=55.0)
    assert cache.misses == 2                       # new δ → new key
    eng.run_until_done()


def test_engine_per_request_objective(small_lm):
    """Requests carry a planning objective; the engine tracks the dominant
    one across queued + in-flight traffic and rejects unknown metrics."""
    cfg, model, params = small_lm
    eng = ServingEngine(model, params, max_batch=2, max_len=32)
    assert eng.dominant_objective() == "latency"      # empty engine default
    eng.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=2)
    eng.submit(np.asarray([4, 5], np.int32), max_new_tokens=2,
               objective="energy")
    eng.submit(np.asarray([6], np.int32), max_new_tokens=2,
               objective="energy")
    assert eng.dominant_objective() == "energy"
    with pytest.raises(ValueError):
        eng.submit(np.asarray([7], np.int32), objective="throughput")
    done = eng.run_until_done()
    assert len(done) == 3
    assert eng.dominant_objective() == "latency"      # drained → default


def test_fleet_epoch_resizes_elastic_world():
    """The fleet → runtime wiring (ISSUE 6): a FleetController membership
    epoch drives ElasticController.on_epoch end-to-end — a departed node
    shrinks the elastic world (the mesh loses its pod axis), the return
    grows it back, and telemetry records every transition."""
    from repro.configs import get_config
    from repro.core.edge_models import paper_cluster
    from repro.fleet import ChurnTrace, FleetController
    from repro.models import build_model
    from repro.models.config import SHAPES
    from repro.runtime.elastic import ElasticController
    from repro.sharding.plan import MULTI_POD
    from repro.telemetry import TelemetryRecorder

    rec = TelemetryRecorder("elastic")
    ctl = ElasticController(build_model(get_config("gemma-2b")),
                            SHAPES["train_4k"], MULTI_POD, telemetry=rec)
    assert ctl.initial_plan().mesh.n_pods == 2
    fleet = FleetController(
        paper_cluster(2),
        ChurnTrace.scripted([(1.0, "tx2", "leave"), (2.0, "tx2", "join")]),
        on_epoch=ctl.on_epoch, telemetry=rec)
    fleet.advance(1.5)                      # tx2 leaves → world of 1
    assert ctl.current_plan.mesh.n_pods == 1 and ctl.replans == 1
    fleet.advance(2.5)                      # tx2 returns → world of 2
    assert ctl.current_plan.mesh.n_pods == 2 and ctl.replans == 2
    worlds = [e for e in rec.events if e.name == "elastic.world"]
    assert [e.value for e in worlds] == [1.0, 2.0]
    assert [e.epoch for e in worlds] == [1, 2]
    members = [e for e in rec.events if e.name == "fleet.membership"]
    assert [(e.value, e.epoch) for e in members] == [(1.0, 1), (2.0, 2)]
    assert len([e for e in rec.events if e.name == "elastic.replan"]) == 2


def test_serve_refuses_to_run_off_the_chip():
    """The serve entry point never falls back to the CPU."""
    from repro.launch import serve
    with pytest.raises(SystemExit, match="platform 'cpu'"):
        serve.main([])


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and the code sets no other directory;
    without it the cache sits at the fixed <repo>/.jax_cache."""
    from repro.launch import serve
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert serve.place_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = serve.place_compile_cache()
        assert path == str(serve.REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_seeded_prompts_are_reproducible_and_in_range():
    from repro.launch.serve import seeded_prompts
    a = seeded_prompts(256000, 8, (16, 512), seed=0)
    b = seeded_prompts(256000, 8, (16, 512), seed=0)
    assert [p.tolist() for p in a] == [p.tolist() for p in b]
    assert all(16 <= len(p) <= 512 and p.dtype == np.int32 for p in a)
    assert all(int(p.max()) < 256000 for p in a)


# ----------------------------------------------------------------- spans
# prompt lengths 3, 4, 3, 5: the second prompt of length 3 reuses the
# prefill program the first one made
SPAN_PROMPTS = ([1, 2, 3], [9, 8, 7, 6], [4, 4, 1], [11, 3, 5, 2, 1])


def _serve_steps(eng) -> int:
    for p in SPAN_PROMPTS:
        eng.submit(np.asarray(p, np.int32), max_new_tokens=3)
    steps = 0
    while eng.queue or eng.active():
        eng.step()
        steps += 1
    return steps


def _profiled_spans(logdir, run):
    """Runs ``run`` under the profiler; returns its ``engine.*`` host
    events as (name, start_ns, end_ns, stats), in order of start."""
    import glob
    import os

    jax.profiler.start_trace(str(logdir))
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(logdir), "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats))
                   for plane in data.planes if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith("engine.")),
                  key=lambda e: e[1])


def _inside(spans, outer, name):
    return [s for s in spans if s[0] == name
            and outer[1] <= s[1] and s[2] <= outer[2]]


def test_engine_spans_land_in_the_profiler_trace(small_lm, tmp_path):
    """Each step's parts are spans in the profiler's own trace: a step holds
    its admission, decode and sample in that order; every admitted request
    has one prefill, slot write and first token, with its id and length;
    ``new_program`` marks only a prompt length's first prefill."""
    cfg, model, params = small_lm
    eng = ServingEngine(model, params, max_batch=2, max_len=32)
    steps = []
    spans = _profiled_spans(tmp_path,
                            lambda: steps.append(_serve_steps(eng)))
    step_spans = [s for s in spans if s[0] == "engine.step"]
    assert len(step_spans) == steps[0] > 0
    for st in step_spans:
        parts = [_inside(spans, st, n) for n in
                 ("engine.admit", "engine.decode", "engine.sample")]
        assert [len(p) for p in parts] == [1, 1, 1]
        admit, decode, sample = (p[0] for p in parts)
        assert admit[2] <= decode[1] and decode[2] <= sample[1]
        for child in ("engine.decode_inputs", "engine.decode_dispatch",
                      "engine.decode_wait"):
            assert len(_inside(spans, decode, child)) == 1
        assert decode[3]["active"] >= 1
    for name in ("engine.prefill", "engine.write_slot", "engine.first_token"):
        got = sorted((s[3]["request"], s[3]["plen"])
                     for s in spans if s[0] == name)
        assert got == [(i, len(p)) for i, p in enumerate(SPAN_PROMPTS)], name
        for s in spans:
            if s[0] == name:
                st, = [t for t in step_spans if t[1] <= s[1] and s[2] <= t[2]]
                assert len(_inside(spans, st, "engine.admit")) == 1
    prefills = [s[3] for s in spans if s[0] == "engine.prefill"]
    assert [p["new_program"] for p in prefills] == [1, 1, 0, 1]
    assert all(p["queued_ms"] >= 0 for p in prefills)


def test_engine_spans_rebuild_one_tree_per_step(small_lm):
    """With a recorder wired, the same spans nest into one tree per step,
    a request's spans sharing its id."""
    from repro.telemetry import span_trees

    cfg, model, params = small_lm
    rec = TelemetryRecorder("engine")
    eng = ServingEngine(model, params, max_batch=2, max_len=32,
                        telemetry=rec)
    steps = _serve_steps(eng)
    roots = span_trees(rec.events)
    assert [r.name for r in roots] == ["engine.step"] * steps
    admitted = []
    for root in roots:
        assert [c.name for c in root.children] == [
            "engine.admit", "engine.decode", "engine.sample"]
        admit, decode, _ = root.children
        assert [c.name for c in decode.children] == [
            "engine.decode_inputs", "engine.decode_dispatch",
            "engine.decode_wait"]
        names = [c.name for c in admit.children]
        assert names == ["engine.prefill", "engine.write_slot",
                         "engine.first_token"] * (len(names) // 3)
        for i in range(0, len(names), 3):
            ids = {c.event.attrs["request"]
                   for c in admit.children[i:i + 3]}
            assert len(ids) == 1
            admitted += ids
        assert all(n.event.wall_s is not None for n in root.walk())
    assert admitted == list(range(len(SPAN_PROMPTS)))


def test_engine_without_recorder_records_nothing(small_lm):
    """No recorder (or a disabled one): the engine keeps no record of its
    steps on the host."""
    cfg, model, params = small_lm
    rec = TelemetryRecorder("engine", enabled=False)
    eng = ServingEngine(model, params, max_batch=2, max_len=32,
                        telemetry=rec)
    assert _serve_steps(eng) > 0 and len(eng.completed) == len(SPAN_PROMPTS)
    assert eng.telemetry is None and rec.events == []
    assert not hasattr(eng, "trace")


@pytest.fixture(scope="module")
def small_ssm():
    cfg = get_config("mamba2-780m").reduced()
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(4))


def _span_attrs(rec, name):
    return [e.attrs for e in rec.events if e.name == name]


def test_ssm_spans_carry_the_recurrent_state_bytes(small_ssm, tmp_path):
    """A state-space engine's slot write stores one slot of ``h`` and of
    the convolution's context and no keys or values; each decode step
    carries the state of its active slots, and updates every slot's state
    in place.  The attributes reach the recorder and the profiler's trace
    alike."""
    cfg, model, params = small_ssm
    rec = TelemetryRecorder("engine")
    eng = ServingEngine(model, params, max_batch=3, max_len=32,
                        telemetry=rec)
    h, conv = eng.cache["h"], eng.cache["conv"]
    assert h.dtype == jnp.float32
    slot = (h.size * 4 + conv.size * conv.dtype.itemsize) // 3
    assert slot == (cfg.n_layers * cfg.ssm.n_heads(cfg.d_model)
                    * cfg.ssm.head_dim * cfg.ssm.d_state * 4
                    + cfg.n_layers * (cfg.ssm.conv_width - 1)
                    * (cfg.ssm.d_inner(cfg.d_model) + 2 * cfg.ssm.d_state)
                    * conv.dtype.itemsize)
    spans = _profiled_spans(tmp_path, lambda: _serve_steps(eng))
    writes = _span_attrs(rec, "engine.write_slot")
    assert len(writes) == len(SPAN_PROMPTS)
    assert all((w["state_bytes"], w["kv_bytes"]) == (slot, 0)
               for w in writes)
    decodes = _span_attrs(rec, "engine.decode")
    assert decodes and all(d["state_bytes"] == d["active"] * slot
                           for d in decodes)
    assert {d["active"] for d in decodes} >= {1, 3}
    assert all(d["in_place_bytes"] == h.nbytes + conv.nbytes == 3 * slot
               for d in decodes)
    traced = [s[3] for s in spans if s[0] == "engine.decode"]
    assert [(int(t["active"]), int(t["state_bytes"]),
             int(t["in_place_bytes"])) for t in traced] == [
        (d["active"], d["state_bytes"], d["in_place_bytes"])
        for d in decodes]
    assert all(int(s[3]["state_bytes"]) == slot for s in spans
               if s[0] == "engine.write_slot")


def test_dense_spans_carry_the_kv_bytes_written(small_lm):
    """A dense engine's slot write stores the prompt's keys and values and
    no recurrent state; its decode steps carry none and update no cache in
    place."""
    cfg, model, params = small_lm
    rec = TelemetryRecorder("engine")
    eng = ServingEngine(model, params, max_batch=2, max_len=32,
                        telemetry=rec)
    _serve_steps(eng)
    per_token = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.hd * 2   # K and V
    writes = _span_attrs(rec, "engine.write_slot")
    assert [(w["plen"] * per_token, 0) for w in writes] == [
        (w["kv_bytes"], w["state_bytes"]) for w in writes]
    decodes = _span_attrs(rec, "engine.decode")
    assert decodes and all((d["state_bytes"], d["in_place_bytes"]) == (0, 0)
                           for d in decodes)


def _copies_of(text: str, a) -> list[str]:
    """The instructions of compiled HLO ``text`` that copy a whole array
    of ``a``'s shape."""
    dims = ",".join(map(str, a.shape))
    return re.findall(rf"%\S*copy\S* = \w+\[{dims}\]", text)


@pytest.mark.parametrize("arch", ["mamba2-780m", "hymba-1.5b"])
def test_decode_updates_the_recurrent_state_in_place(arch):
    """The engine's donated decode program carries the stacked ``h``
    through its layer loop and updates it in place: no copy of the whole
    stack (after the loop, or in every layer where the old slice has a
    second reader), and no temporary as large as it.  Eight layers make
    the state outweigh the step's other temporaries; the hybrid's keys and
    values still pass through the loop's outputs, so its cache is kept
    short."""
    cfg = dataclasses.replace(get_config(arch).reduced(), n_layers=8)
    model = build_model(cfg)
    eng = ServingEngine(model, model.init(jax.random.PRNGKey(6)),
                        max_batch=32, max_len=2)
    batch = {"tokens": jnp.zeros((32, 1), jnp.int32),
             "lengths": jnp.ones((32,), jnp.int32)}
    compiled = eng._decode.lower(eng.params, eng.cache, batch).compile()
    h = eng.cache["h"]
    assert _copies_of(compiled.as_text(), h) == []
    assert compiled.memory_analysis().temp_size_in_bytes < h.nbytes
