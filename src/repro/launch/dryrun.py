import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, prove memory fit, and extract the roofline terms.

For each cell:
  1. HiDP plans the cell (tier-1 global DP over pods, tier-2 layout DSE).
  2. The step function (train / prefill / decode per the shape's kind) is
     jit'd with plan-derived in/out shardings and lowered with
     ShapeDtypeStruct stand-ins — no real allocation anywhere.
  3. ``compiled.memory_analysis()`` proves per-device fit;
     ``compiled.cost_analysis()`` provides HLO FLOPs/bytes; collective
     traffic is parsed from the post-SPMD HLO (per-device shapes).
  4. Everything lands in a JSON record consumed by benchmarks/roofline.py
     and EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]
"""

import argparse
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh
from repro.models import SHAPES, build_model, shape_applicable
from repro.sharding import ctx as shard_ctx
from repro.sharding import specs
from repro.sharding.plan import MULTI_POD, MeshDesc, SINGLE_POD, plan_tpu
from repro.training import optimizer as optim
from repro.training.train_loop import make_train_step

COLLECTIVE_RE = re.compile(
    r"^\s*%?\S+\s*=\s*(\([^)]*\)|\S+)\s*(all-gather|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute)", re.M)
SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s32|u32|s64|u64|s16|u16|s8|u8|pred)"
                      r"\[([0-9,]*)\]")
DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
               "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1}


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in the (post-SPMD,
    per-device) HLO.  Returns totals per op kind."""
    out: dict[str, float] = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        shapes_blob, kind = m.group(1), m.group(2)
        nbytes = 0.0
        for sm in SHAPE_RE.finditer(shapes_blob):
            dt, dims = sm.group(1), sm.group(2)
            numel = 1
            for d in dims.split(","):
                if d:
                    numel *= int(d)
            nbytes += numel * DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0.0) + nbytes
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def build_cell(arch: str, shape_name: str, mesh_desc: MeshDesc,
               force_layout=None, moe_impl=None, force_global=None):
    cfg = get_config(arch)
    model = build_model(cfg)
    shape = SHAPES[shape_name]
    plan = plan_tpu(model, shape, mesh_desc, force_layout=force_layout,
                    moe_impl=moe_impl, force_global=force_global)
    return cfg, model, shape, plan


def _plan_act_specs(plan):
    from jax.sharding import PartitionSpec as P

    def ax(axes):
        return (None if not axes
                else axes[0] if len(axes) == 1 else tuple(axes))
    act = P(ax(plan.batch_axes), ax(plan.seq_axes), None)
    logits = P(ax(plan.batch_axes), None, ax(plan.tp_axes))
    return act, logits


def lower_cell(model, shape, plan, mesh):
    """Returns the lowered computation for the cell's step function.  The
    plan's activation/logits layouts are published to the sharding context so
    the model pins them with with_sharding_constraint at layer boundaries."""
    act_spec, logits_spec = _plan_act_specs(plan)
    ep_axis = "model" if "model" in mesh.axis_names else (
        plan.tp_axes[0] if plan.tp_axes else mesh.axis_names[-1])
    with shard_ctx.plan_specs(act_spec, logits_spec, mesh=mesh,
                              ep_axis=ep_axis):
        return _lower_cell_inner(model, shape, plan, mesh)


def _lower_pipeline_train(model, shape, plan, mesh, in_specs):
    """GPipe rendering of global model-mode for training shapes: stacked
    layer params reshaped (S, L/S, ...) and sharded over 'pod'; microbatches
    stream through ppermute ticks (sharding/pipeline.py).  Reference
    implementation: stage-resident weights (no FSDP composition)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.sharding import pipeline as pp

    cfg = model.cfg
    S = plan.pipeline_stages
    params = model.param_specs(jnp.float32)
    per = cfg.n_layers // S
    staged = dict(params)
    staged["layers"] = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((S, per) + tuple(s.shape[1:]),
                                       s.dtype), params["layers"])
    p_sh = pp.stage_param_shardings(mesh, staged, axis="pod")
    sd = jnp.bfloat16 if plan.opt_dtype == "bfloat16" else jnp.float32
    opt = optim.init_abstract(staged, sd)
    o_sh = optim.OptState(step=specs.replicated(mesh), m=p_sh, v=p_sh)
    step = pp.make_pipeline_train_step(
        model, optim.OptConfig(state_dtype=plan.opt_dtype), plan, mesh)
    batch_sh = {k: specs.replicated(mesh) for k in in_specs}
    metric_sh = {k: specs.replicated(mesh)
                 for k in ("grad_norm", "lr", "loss")}
    fn = jax.jit(step, in_shardings=(p_sh, o_sh, batch_sh),
                 out_shardings=(p_sh, o_sh, metric_sh),
                 donate_argnums=(0, 1))
    return fn.lower(staged, opt, in_specs)


def _lower_cell_inner(model, shape, plan, mesh):
    cfg = model.cfg
    in_specs = model.input_specs(shape)
    batch_sh = specs.batch_shardings(mesh, in_specs, plan)
    if (shape.kind == "train" and plan.pipeline_stages > 1
            and cfg.family in ("dense", "moe", "ssm", "hybrid")):
        return _lower_pipeline_train(model, shape, plan, mesh, in_specs)
    if shape.kind == "train":
        master = plan.param_dtype == "bfloat16"
        params = model.param_specs(
            jnp.bfloat16 if master else jnp.float32)
        p_sh = specs.param_shardings(mesh, params, plan)
        sd = jnp.bfloat16 if plan.opt_dtype == "bfloat16" else jnp.float32
        opt = optim.init_abstract(params, sd, master=master)
        o_sh = optim.OptState(step=specs.replicated(mesh),
                              m=p_sh, v=p_sh,
                              master=p_sh if master else None)
        step = make_train_step(
            model, optim.OptConfig(state_dtype=plan.opt_dtype), plan)
        metric_sh = {"grad_norm": specs.replicated(mesh),
                     "lr": specs.replicated(mesh),
                     "loss": specs.replicated(mesh)}
        fn = jax.jit(step,
                     in_shardings=(p_sh, o_sh, batch_sh),
                     out_shardings=(p_sh, o_sh, metric_sh),
                     donate_argnums=(0, 1))
        return fn.lower(params, opt, in_specs)
    params = model.param_specs(jnp.bfloat16)
    p_sh = specs.param_shardings(mesh, params, plan)
    if shape.kind == "prefill":
        def prefill(p, b):
            return model.apply_prefill(p, b, moe_impl=plan.moe_impl)
        cache_like = model.cache_specs(shape)
        c_sh = specs.cache_shardings(mesh, cache_like, plan)
        lsh = specs.logits_sharding(
            mesh, plan, (shape.global_batch, 1, cfg.vocab))
        # prefill's returned cache has seq = input length
        fn = jax.jit(prefill, in_shardings=(p_sh, batch_sh),
                     out_shardings=(lsh, c_sh))
        return fn.lower(params, in_specs)
    # decode
    cache = model.cache_specs(shape)
    c_sh = specs.cache_shardings(mesh, cache, plan)

    def decode(p, c, b):
        return model.apply_decode(p, c, b, moe_impl=plan.moe_impl)
    lsh = specs.logits_sharding(mesh, plan,
                                (shape.global_batch, 1, cfg.vocab))
    fn = jax.jit(decode, in_shardings=(p_sh, c_sh, batch_sh),
                 out_shardings=(lsh, c_sh),
                 donate_argnums=(1,))
    return fn.lower(params, cache, in_specs)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             force_layout=None, moe_impl=None, force_global=None,
             out_dir: str = "experiments/dryrun") -> dict:
    mesh_desc = MULTI_POD if multi_pod else SINGLE_POD
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "x".join(map(str, mesh_desc.shape)),
           "multi_pod": multi_pod}
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    t0 = time.time()
    cfg, model, shape, plan = build_cell(arch, shape_name, mesh_desc,
                                         force_layout, moe_impl, force_global)
    mesh = make_production_mesh(multi_pod=multi_pod)
    with mesh:
        lowered = lower_cell(model, shape, plan, mesh)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text())
    rec.update(
        status="ok",
        plan=dict(global_mode=plan.global_mode, layout=plan.local_layout,
                  batch_axes=plan.batch_axes, seq_axes=plan.seq_axes,
                  tp_axes=plan.tp_axes, fsdp_axes=plan.fsdp_axes,
                  microbatches=plan.microbatches, moe_impl=plan.moe_impl,
                  remat_group=plan.remat_group, opt_dtype=plan.opt_dtype,
                  param_dtype=plan.param_dtype,
                  pipeline_stages=plan.pipeline_stages,
                  predicted={k: v for k, v in plan.predicted.items()
                             if k != "fits"},
                  planning_ms=plan.planning_seconds * 1e3),
        memory=dict(
            argument_bytes=mem.argument_size_in_bytes,
            output_bytes=mem.output_size_in_bytes,
            temp_bytes=mem.temp_size_in_bytes,
            alias_bytes=mem.alias_size_in_bytes,
            peak_per_device=(mem.argument_size_in_bytes
                             + mem.output_size_in_bytes
                             + mem.temp_size_in_bytes
                             - mem.alias_size_in_bytes)),
        cost=dict(flops=cost.get("flops", -1.0),
                  bytes_accessed=cost.get("bytes accessed", -1.0),
                  transcendentals=cost.get("transcendentals", -1.0)),
        collectives=coll,
        model_flops=model.step_flops(shape),
        seconds=dict(lower=t_lower, compile=t_compile),
    )
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}_{shape_name}_{'mp' if multi_pod else 'sp'}"
        if force_layout:
            tag += f"_{force_layout}"
        if moe_impl:
            tag += f"_{moe_impl}"
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=2)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (arch × shape) on the selected mesh")
    ap.add_argument("--layout", default=None,
                    help="force a tier-2 layout candidate (hillclimb)")
    ap.add_argument("--moe-impl", default=None,
                    choices=["dense", "ep_a2a", "ep_a2a_q8"])
    ap.add_argument("--force-global", default=None,
                    choices=["data", "model"])
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args(argv)

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape (or --all) required")
        cells = [(args.arch, args.shape)]

    failures = 0
    for arch, shape in cells:
        try:
            rec = run_cell(arch, shape, args.multi_pod,
                           force_layout=args.layout, moe_impl=args.moe_impl,
                           force_global=args.force_global, out_dir=args.out)
            if rec["status"] == "ok":
                m = rec["memory"]["peak_per_device"] / 1e9
                print(f"[OK] {arch:22s} {shape:12s} "
                      f"{rec['mesh']:9s} layout={rec['plan']['layout']:12s} "
                      f"peak={m:6.2f}GB flops={rec['cost']['flops']:.3e} "
                      f"coll={rec['collectives'].get('total', 0)/1e9:.2f}GB "
                      f"compile={rec['seconds']['compile']:.0f}s",
                      flush=True)
            else:
                print(f"[SKIP] {arch:22s} {shape:12s} — {rec['reason']}",
                      flush=True)
        except Exception as e:
            failures += 1
            print(f"[FAIL] {arch:22s} {shape:12s}: "
                  f"{type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
