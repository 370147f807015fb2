"""Distributed-execution equivalence on a multi-device CPU mesh: sharded
runs must match single-device runs bit-for-bit-ish; the GPipe pipeline must
match the flat stack; EP MoE must match dense MoE.

These tests spawn a subprocess with XLA_FLAGS=8 host devices so the main
test session keeps its single-device view (per the dry-run contract).
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_train_step_matches_single_device():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.configs import get_config
        from repro.launch.mesh import make_mesh
        from repro.models import build_model
        from repro.sharding.plan import ShardingPlan, MeshDesc
        from repro.sharding import specs, ctx as shard_ctx
        from repro.training import optimizer as optim
        from repro.training.train_loop import make_train_step

        cfg = get_config("gemma-2b").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32),
                                              0, cfg.vocab),
                 "targets": jax.random.randint(jax.random.PRNGKey(2), (8, 32),
                                               0, cfg.vocab)}
        mesh_desc = MeshDesc(("data", "model"), (4, 2))
        plan = ShardingPlan(arch="t", shape="s", mesh=mesh_desc,
                            global_mode="data", local_layout="dp_tp",
                            batch_axes=("data",), tp_axes=("model",),
                            remat=False)
        step = make_train_step(model, optim.OptConfig(lr=1e-3,
                                                      warmup_steps=1), plan)
        # single device
        p1, o1, m1 = step(params, optim.init(params), batch)

        # sharded, on a mesh whose axes leave layouts to XLA's propagation
        mesh = make_mesh((4, 2), ("data", "model"))
        with mesh:
            p_sh = specs.param_shardings(mesh, params, plan)
            b_sh = specs.batch_shardings(mesh, batch, plan)
            params_s = jax.device_put(params, p_sh)
            batch_s = {k: jax.device_put(v, b_sh[k]) for k, v in batch.items()}
            with shard_ctx.plan_specs(P("data", None, None),
                                      P("data", None, "model"), mesh=mesh,
                                      ep_axis="model"):
                p2, o2, m2 = jax.jit(step)(params_s, optim.init(params_s),
                                           batch_s)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=2e-3)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=3e-3)
        print("SHARDED-EQUIV-OK")
    """))


def test_pipeline_matches_flat_stack():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import build_model, SHAPES
        from repro.models import transformer
        from repro.sharding import pipeline
        cfg = get_config("gemma-2b").reduced()   # 2 layers → 2 stages
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                    cfg.vocab)
        hidden_flat, _ = transformer.forward(cfg, params, tokens,
                                             mode="train",
                                             return_hidden=True)
        mesh = jax.make_mesh((2, 4), ("pod", "data"))
        staged = pipeline.stage_params(cfg, params, n_stages=2)
        with mesh:
            got = jax.jit(lambda s, t: pipeline.pipeline_hidden(
                cfg, s, t, mesh=mesh, n_stages=2, microbatches=2))(
                staged, tokens)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(hidden_flat, np.float32),
                                   atol=3e-2, rtol=3e-2)
        print("PIPELINE-OK")
    """))


def test_moe_ep_matches_dense_under_jit_mesh():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.models.config import ArchConfig, MoESpec
        from repro.models import layers as L, moe_ep
        from repro.sharding import ctx as shard_ctx
        cfg = ArchConfig(name="t", family="moe", n_layers=1, d_model=32,
                         n_heads=4, n_kv_heads=2, d_ff=64, vocab=128,
                         moe=MoESpec(num_experts=16, top_k=2, d_ff_expert=48,
                                     capacity_factor=8.0))
        p = L.moe_params(cfg, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32)
                              ).astype(jnp.bfloat16)
        dense = L.moe_dense(cfg, p, x)
        mesh = jax.make_mesh((1, 8), ("data", "model"))
        with mesh:
            with shard_ctx.plan_specs(P("data", None, None), None, mesh=mesh,
                                      ep_axis="model"):
                got = jax.jit(lambda p, x: moe_ep.moe_ep_a2a(cfg, p, x))(p, x)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(dense, np.float32), atol=5e-2)
        print("MOE-EP-OK")
    """))


def test_moe_ep_grads_flow():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.models.config import ArchConfig, MoESpec
        from repro.models import layers as L, moe_ep
        from repro.sharding import ctx as shard_ctx
        cfg = ArchConfig(name="t", family="moe", n_layers=1, d_model=16,
                         n_heads=2, n_kv_heads=1, d_ff=32, vocab=64,
                         moe=MoESpec(num_experts=8, top_k=2, d_ff_expert=24,
                                     capacity_factor=8.0))
        p = L.moe_params(cfg, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
        mesh = jax.make_mesh((1, 8), ("data", "model"))
        with mesh:
            with shard_ctx.plan_specs(P("data", None, None), None, mesh=mesh,
                                      ep_axis="model"):
                g = jax.jit(jax.grad(lambda p, x: jnp.sum(
                    moe_ep.moe_ep_a2a(cfg, p, x).astype(jnp.float32) ** 2)))(
                    p, x)
        norms = [float(jnp.abs(l).sum()) for l in jax.tree.leaves(g)]
        assert sum(norms) > 0, norms
        assert all(np.isfinite(n) for n in norms)
        print("MOE-EP-GRAD-OK")
    """))
