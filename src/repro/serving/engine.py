"""Serving engine: continuous batching over a slotted KV cache, driven by the
HiDP plan.

The engine is the TPU rendering of the paper's Run-time Scheduler FSM
(Fig. 4): ANALYZE admits queued requests into free slots, EXPLORE is the
HiDP planning pass (amortized by the shared multi-tenant ``PlanCache`` —
one frontier pass per tenant, re-entered per tenant on drift/elasticity
events), OFFLOAD/MAP dispatch the jitted prefill/decode executables with
plan-derived shardings, EXECUTE streams decode steps and merges emitted
tokens per request (Alg. 1 line 13).

Each ``submit`` may name its tenant (``dag=``, a ModelDAG) and objective;
the request's plan is resolved from the cache's warm frontier — see
docs/serving.md for the full multi-tenant lifecycle.

Runs identically on a CPU test mesh (tiny configs) and the production mesh.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fingerprint import dag_fingerprint
from repro.core.objective import METRICS
from repro.core.scheduler import State
from repro.models.model import Model
from repro.models.transformer import STATE_KEYS
from repro.telemetry import active, host_span

#: cache entries held per position of a slot (attention's keys and values)
KV_KEYS = ("k", "v", "xk", "xv")


@dataclasses.dataclass
class Request:
    request_id: int
    prompt: np.ndarray                  # (P,) int32
    max_new_tokens: int = 16
    eos_id: int | None = None
    # what this request asks the planner to minimize when (re-)planning:
    # "latency" | "energy" | "edp" (an Objective's metric name)
    objective: str = "latency"
    # which tenant (ModelDAG) this request belongs to — resolved against
    # the shared PlanCache; None when the engine serves without a cache
    dag: Any = None
    # host time (time.perf_counter) at submit
    submitted: float = 0.0
    # filled during serving
    slot: int | None = None
    generated: list[int] = dataclasses.field(default_factory=list)
    done: bool = False


class ServingEngine:
    """``feedback`` (a ``repro.profiling.FeedbackLoop``) closes the paper's
    ANALYZE↔EXECUTE loop at serving time: every decode step's wall-clock
    latency is reported as an observation keyed ``engine/decode``; when the
    loop flags drift the engine re-enters EXPLORE (traced, counted in
    ``replans``) and calls ``on_replan`` — typically
    ``ElasticController.on_drift`` or a fresh HiDP planning pass.

    Requests carry a per-request planning *objective* (``"latency"`` |
    ``"energy"`` | ``"edp"``, see ``repro.core.Objective``): the engine
    itself executes whatever plan it is given, but it tracks what the
    in-flight traffic asked for and exposes :meth:`dominant_objective` so an
    ``on_replan`` callback can hand the right ``Objective`` to the next
    planning pass (e.g. battery-saver clients requesting ``energy`` flip the
    fleet to energy-optimal plans once they dominate the batch).

    ``plan_cache`` (a ``repro.serving.plan_cache.PlanCache``) puts planning
    on the shared multi-tenant frontier cache: every ``submit`` names its
    tenant with ``dag=`` (a ModelDAG; ``default_dag`` covers single-tenant
    deployments) and resolves its objective against that tenant's cached
    front — zero DP work after each tenant's first request.  A drift event
    re-enters EXPLORE with exactly **one frontier re-plan per in-flight
    tenant**, each selected at that tenant's dominant objective
    (:meth:`dominant_objective`); per-tenant selections land in
    ``tenant_plans`` keyed by dag fingerprint.  Wire the same ``feedback``
    loop as the cache's ``version_source`` and the bump is atomic with the
    refit.

    Under churn (``repro.fleet``), wire a ``FleetController``'s
    ``on_epoch`` to :meth:`on_membership_change` and give the cache the
    controller as its ``membership_source``: every membership epoch then
    re-enters EXPLORE with one plan resolution per in-flight tenant — a
    single frontier pass for a never-seen membership, a pure warm hit for
    a returning one (see docs/fleet.md).

    ``telemetry`` (a ``repro.telemetry.TelemetryRecorder``) records every
    submit's per-tenant cache resolution (hit | miss | none) and every
    EXPLORE re-entry (drift or membership epoch) as structured counters —
    see docs/observability.md.

    Every ``step`` marks its parts as spans (``repro.telemetry.host_span``):
    ``engine.step`` holds ``engine.admit`` (with one ``engine.prefill``,
    ``engine.write_slot`` and ``engine.first_token`` per admitted request,
    each carrying ``request`` and ``plen``; the slot write also carries the
    bytes it stores, ``state_bytes`` of recurrent state and ``kv_bytes`` of
    keys and values), ``engine.decode`` (carrying ``active``,
    ``state_bytes``, the recurrent state of the active slots, and
    ``in_place_bytes``, the cache the step updates in place in its donated
    buffer, every slot's recurrent state; with
    ``engine.decode_inputs``, ``engine.decode_dispatch`` and
    ``engine.decode_wait``) and ``engine.sample``.  They land in an open
    ``jax.profiler`` trace beside the device's work, where the jitted
    programs run as ``jit_engine_prefill`` and ``jit_engine_decode``; with
    a recorder wired they are recorded there too."""

    def __init__(self, model: Model, params: dict, *, max_batch: int = 4,
                 max_len: int = 128, plan=None, donate: bool = True,
                 feedback=None, on_replan: Callable[[], Any] | None = None,
                 plan_cache=None, default_dag=None, telemetry=None):
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.plan = plan
        self.feedback = feedback
        self.on_replan = on_replan
        self.telemetry = active(telemetry)
        if plan_cache is None and default_dag is not None:
            raise ValueError(
                "default_dag names the tenant submits resolve against a "
                "plan_cache; without a cache there is nothing to resolve "
                "— pass plan_cache too")
        self.plan_cache = plan_cache
        self.default_dag = default_dag
        # most recent plan selection per tenant, keyed by dag fingerprint,
        # and each tenant's compute intensity (part of its cache key)
        self.tenant_plans: dict[str, Any] = {}
        self._tenant_deltas: dict[str, float | None] = {}
        self.replans = 0
        self._decode_steps = 0
        self.cache = model.init_cache(max_batch, max_len)
        # every slot's recurrent state, in bytes (0 where the model has
        # none): the decode step carries it and updates it in place
        self._in_place_bytes = sum(self.cache[k].nbytes
                                   for k in STATE_KEYS if k in self.cache)
        self._slot_state_bytes = self._in_place_bytes // max_batch
        self.lengths = np.zeros((max_batch,), np.int32)
        self.slot_req: list[Request | None] = [None] * max_batch
        self.queue: deque[Request] = deque()
        self.completed: dict[int, Request] = {}
        self._next_id = 0
        self.state = State.ANALYZE

        # a named function, so that the program runs under a stable name
        # (jit_engine_decode; jit_engine_prefill below) in a profiler trace
        def engine_decode(params, cache, batch):
            return model.apply_decode(params, cache, batch)

        self._decode = jax.jit(engine_decode,
                               donate_argnums=(1,) if donate else ())
        self._prefill_cache: dict[int, Callable] = {}

    # ------------------------------------------------------------------ API
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16,
               eos_id: int | None = None, objective: str = "latency",
               dag=None, delta: float | None = None) -> int:
        """Queue one request.  ``objective`` names the planning metric this
        request wants (``"latency"`` | ``"energy"`` | ``"edp"``); ``dag``
        names its tenant (falling back to ``default_dag``) and ``delta``
        the tenant's compute intensity — part of the cache key, so it must
        match what warmed (or persisted) the tenant's front; None uses the
        cache planner's default.  With a ``plan_cache`` wired, the
        objective is resolved against that tenant's cached frontier right
        here — a lookup + select, no DP pass after the tenant's first
        request.  ``self.plan`` tracks the most recent resolution;
        per-tenant selections live in ``tenant_plans``."""
        if objective not in METRICS:
            raise ValueError(f"unknown objective {objective!r}; "
                             f"expected one of {METRICS}")
        dag = dag if dag is not None else self.default_dag
        if dag is not None and self.plan_cache is None:
            raise ValueError(
                "submit(dag=...) names a tenant to resolve against a "
                "plan_cache, but the engine has none — wire plan_cache=")
        rid = self._next_id
        self._next_id += 1
        if self.plan_cache is not None:
            if dag is None:
                raise ValueError(
                    "a plan_cache is wired but this submit names no "
                    "tenant: pass dag= here or default_dag= to the engine")
            misses0 = self.plan_cache.misses
            # the resolve context roots this submit's trace subtree: the
            # cache's hit/miss counters and any frontier-pass span it
            # triggers auto-parent under it
            with host_span("engine.resolve", self.telemetry,
                           tenant=dag.name, request=rid,
                           objective=objective):
                self.plan = self.plan_cache.get(dag, objective=objective,
                                                delta=delta)
                fp = dag_fingerprint(dag)
                self.tenant_plans[fp] = self.plan
                self._tenant_deltas[fp] = delta
                if self.telemetry is not None:
                    # per-tenant cache resolution: was this submit served
                    # off the warm front, or did it pay the tenant's DP
                    # pass?
                    self.telemetry.counter(
                        "engine.submit", tenant=dag.name, request=rid,
                        objective=objective,
                        resolved="miss" if self.plan_cache.misses > misses0
                        else "hit")
        elif self.telemetry is not None:
            self.telemetry.counter("engine.submit", request=rid,
                                   objective=objective, resolved="none")
        self.queue.append(Request(rid, np.asarray(prompt, np.int32),
                                  max_new_tokens, eos_id,
                                  objective=objective, dag=dag,
                                  submitted=time.perf_counter()))
        return rid

    def active(self) -> int:
        return sum(r is not None for r in self.slot_req)

    def _requests(self):
        """Queued + in-flight requests, queue first."""
        yield from self.queue
        for r in self.slot_req:
            if r is not None:
                yield r

    def _tenant_traffic(self) -> dict:
        """``{dag fingerprint: (dag, request count)}`` over queued +
        in-flight requests."""
        by_fp: dict[str, Any] = {}
        for r in self._requests():
            if r.dag is not None:
                fp = dag_fingerprint(r.dag)
                dag, n = by_fp.get(fp, (r.dag, 0))
                by_fp[fp] = (dag, n + 1)
        return by_fp

    def tenant_dags(self) -> list:
        """The distinct tenants with queued or in-flight traffic, ordered
        by dag fingerprint so per-tenant re-plans (and therefore cache
        behaviour) are deterministic regardless of arrival order."""
        traffic = self._tenant_traffic()
        return [traffic[fp][0] for fp in sorted(traffic)]

    def dominant_objective(self, dag=None) -> str:
        """The most-requested objective among queued + in-flight requests —
        what an ``on_replan`` callback (and the post-drift cache re-plan)
        hands the next planning pass.  ``dag`` restricts the count to one
        tenant's traffic (how each tenant's drift re-plan picks its own
        objective).  Tie-breaking is deterministic by the fixed ``METRICS``
        order (latency > energy > edp; empty engine → "latency"), so
        re-plan objectives — and therefore cache behaviour — are
        reproducible across runs regardless of dict or arrival order."""
        fp = None if dag is None else dag_fingerprint(dag)
        counts = dict.fromkeys(METRICS, 0)
        for r in self._requests():
            if fp is None or (r.dag is not None
                              and dag_fingerprint(r.dag) == fp):
                counts[r.objective] += 1
        return max(METRICS, key=counts.__getitem__)

    def _replan_in_flight_tenants(self) -> None:
        """One cache resolution per in-flight tenant, each at that tenant's
        dominant objective and keyed delta; the engine-level plan follows
        the busiest tenant (ties break low-fingerprint-first), never an
        arbitrary last writer."""
        traffic = self._tenant_traffic()
        for fp in sorted(traffic):
            dag = traffic[fp][0]
            self.tenant_plans[fp] = self.plan_cache.get(
                dag, objective=self.dominant_objective(dag),
                delta=self._tenant_deltas.get(fp))
        if traffic:
            busiest = max(sorted(traffic), key=lambda f: traffic[f][1])
            self.plan = self.tenant_plans[busiest]

    def on_membership_change(self, epoch=None) -> None:
        """The fleet's membership moved (a ``repro.fleet.FleetController``
        epoch — wire this as its ``on_epoch`` callback): re-enter EXPLORE
        with exactly one plan resolution per in-flight tenant.  Unlike
        drift, nothing is invalidated — the cache key's membership
        fingerprint changed under us, so a brand-new membership costs one
        frontier pass per affected tenant while a *returning* membership
        (a node that left and came back) resolves warm with zero DP work.
        ``epoch`` (the :class:`~repro.fleet.MembershipEpoch`) is accepted
        and ignored so the callback wires directly."""
        self.state = State.EXPLORE
        self.replans += 1
        # one trace subtree per EXPLORE re-entry: the replan counter and
        # every per-tenant resolution (warm hit or frontier pass) parent
        # under it
        with host_span("engine.replan_pass", self.telemetry,
                       reason="epoch", epoch=getattr(epoch, "epoch", None)):
            if self.telemetry is not None:
                self.telemetry.counter(
                    "engine.replan", reason="epoch",
                    epoch=getattr(epoch, "epoch", None),
                    tenants=len(self._tenant_traffic()))
            if self.plan_cache is not None:
                self._replan_in_flight_tenants()
            if self.on_replan is not None:
                self.on_replan()

    def run_until_done(self, max_steps: int = 10_000) -> dict[int, Request]:
        for _ in range(max_steps):
            if not self.queue and self.active() == 0:
                break
            self.step()
        return self.completed

    # ----------------------------------------------------------------- admit
    def _prefill_fn(self, plen: int) -> Callable:
        if plen not in self._prefill_cache:
            model = self.model

            def engine_prefill(params, batch):
                return model.apply_prefill(params, batch)

            self._prefill_cache[plen] = jax.jit(engine_prefill)
        return self._prefill_cache[plen]

    def _admit(self) -> None:
        self.state = State.ANALYZE
        tel = self.telemetry
        with host_span("engine.admit", tel):
            for slot in range(self.max_batch):
                if self.slot_req[slot] is not None or not self.queue:
                    continue
                req = self.queue.popleft()
                rid, plen = req.request_id, len(req.prompt)
                # a prompt length's first prefill makes its program
                new = plen not in self._prefill_cache
                queued_ms = (time.perf_counter() - req.submitted) * 1e3
                with host_span("engine.prefill", tel,
                               wall_attrs={"queued_ms": queued_ms},
                               request=rid, plen=plen, new_program=int(new)):
                    logits, pcache = self._prefill_fn(plen)(
                        self.params, self._prefill_batch(req))
                with host_span("engine.write_slot", tel, request=rid,
                               plen=plen,
                               state_bytes=self._written(pcache, STATE_KEYS),
                               kv_bytes=self._written(pcache, KV_KEYS)):
                    self._write_slot(slot, pcache, plen)
                with host_span("engine.first_token", tel, request=rid,
                               plen=plen):
                    first = int(jnp.argmax(logits[0, -1]))
                req.slot = slot
                req.generated.append(first)
                self.slot_req[slot] = req
                self.lengths[slot] = plen + 1

    def _prefill_batch(self, req: Request) -> dict:
        plen = len(req.prompt)
        batch = {"tokens": jnp.asarray(req.prompt[None, :])}
        if self.model.cfg.family == "audio":
            batch["frames"] = jnp.zeros(
                (1, max(plen // 2, 1), self.model.cfg.d_model), jnp.bfloat16)
        if self.model.cfg.family == "vlm":
            batch["vision"] = jnp.zeros(
                (1, self.model.cfg.n_vision_tokens, self.model.cfg.d_model),
                jnp.bfloat16)
        batch["lengths"] = jnp.asarray([plen], jnp.int32)
        return batch

    def _written(self, pcache: dict, keys: tuple) -> int:
        """Bytes that writing ``pcache`` into a slot stores for ``keys``."""
        return sum(pcache[k].size * self.cache[k].dtype.itemsize
                   for k in keys if k in self.cache)

    def _write_slot(self, slot: int, pcache: dict, plen: int) -> None:
        """Copy a (L, 1, P, ...) prefill cache into slot ``slot`` of the
        engine cache (padded to max_len)."""
        new = {}
        for k in self.cache:
            dst, src = self.cache[k], pcache[k]
            if k in KV_KEYS:
                # (..., 1, P, H, D) → slot write at seq prefix
                p = src.shape[-3]
                new[k] = dst.at[..., slot, :p, :, :].set(src[..., 0, :p, :, :])
            elif k == "h":
                new[k] = dst.at[..., slot, :, :, :].set(src[..., 0, :, :, :])
            elif k == "conv":
                new[k] = dst.at[..., slot, :, :].set(src[..., 0, :, :])
            else:
                new[k] = dst
        self.cache = new

    # ---------------------------------------------------------------- decode
    def step(self) -> None:
        tel = self.telemetry
        with host_span("engine.step", tel):
            self._admit()
            if self.active() == 0:
                return
            self.state = State.EXECUTE
            active = self.active()
            with host_span("engine.decode", tel, active=active,
                           state_bytes=active * self._slot_state_bytes,
                           in_place_bytes=self._in_place_bytes):
                with host_span("engine.decode_inputs", tel):
                    tokens = np.zeros((self.max_batch, 1), np.int32)
                    for s, req in enumerate(self.slot_req):
                        if req is not None:
                            tokens[s, 0] = req.generated[-1]
                    batch = {"tokens": jnp.asarray(tokens),
                             "lengths": jnp.asarray(
                                 np.maximum(self.lengths, 1))}
                t0 = time.perf_counter()
                with host_span("engine.decode_dispatch", tel):
                    logits, self.cache = self._decode(self.params,
                                                      self.cache, batch)
                with host_span("engine.decode_wait", tel):
                    jax.block_until_ready(logits)
                step_s = time.perf_counter() - t0
            self._decode_steps += 1
            if self.feedback is not None and self._decode_steps > 1:
                # step 1 pays jit compilation — not a hardware signal
                self._observe(step_s)
            with host_span("engine.sample", tel):
                self._sample(logits)

    def _observe(self, step_s: float) -> None:
        """Report one decode step's latency to the feedback loop; on drift,
        re-enter EXPLORE."""
        # work = decoded tokens this step (batch-occupancy proxy for
        # FLOPs; the loop's regressor absorbs the per-token constant)
        drifted = self.feedback.observe(
            "engine/decode", "decode", float(self.active()), 0.0, step_s)
        if not drifted:
            return
        self.state = State.EXPLORE
        self.replans += 1
        with host_span("engine.replan_pass", self.telemetry,
                       reason="drift"):
            if self.telemetry is not None:
                self.telemetry.counter(
                    "engine.replan", reason="drift",
                    tenants=len(self._tenant_traffic()))
            if self.plan_cache is not None:
                # the drift already bumped the calibration version (via
                # version_source or this on_drift); re-plan exactly once
                # *per in-flight tenant* — each tenant's first post-bump
                # lookup is its single frontier pass — at the objective
                # that tenant's traffic wants and the delta its front was
                # keyed under
                self.plan_cache.on_drift()
                self._replan_in_flight_tenants()
            if self.on_replan is not None:
                self.on_replan()

    def _sample(self, logits) -> None:
        """Greedy next tokens for every slot in use; retire the requests
        that are done."""
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        for s, req in enumerate(self.slot_req):
            if req is None:
                continue
            tok = int(nxt[s])
            req.generated.append(tok)
            self.lengths[s] += 1
            over = len(req.generated) >= req.max_new_tokens
            hit_eos = req.eos_id is not None and tok == req.eos_id
            full = self.lengths[s] >= self.max_len
            if over or hit_eos or full:
                req.done = True
                self.completed[req.request_id] = req
                self.slot_req[s] = None
                self.lengths[s] = 0
