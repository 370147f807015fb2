"""Compile the served path's Pallas kernels at real widths for a described
TPU v5e, without a chip: what Mosaic or XLA would refuse on the chip (an
unsupported lowering, a misaligned block, too much VMEM or HBM) fails here.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library, and
every xdist worker imports this file.  Where it cannot be described, the
tests skip.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import ops, ssd_scan
from repro.models import build_model
from repro.sharding import ctx as shard_ctx

GEMMA = get_config("gemma-2b")
MAMBA = get_config("mamba2-780m")


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


def _compile(fn, *shapes):
    """Compiles ``fn`` for the shapes; returns the compiled HLO text."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_flash_attention_compiles_at_gemma_width(one_chip):
    t, hq, hkv, d = 512, GEMMA.n_heads, GEMMA.n_kv_heads, GEMMA.hd
    q = jax.ShapeDtypeStruct((1, t, hq, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, t, hkv, d), jnp.bfloat16, sharding=one_chip)
    assert "tpu_custom_call" in _compile(fa.flash_attention, q, kv, kv)


def test_decode_attention_compiles_at_gemma_width(one_chip):
    b, s, hq, hkv, d = 8, 2048, GEMMA.n_heads, GEMMA.n_kv_heads, GEMMA.hd
    q = jax.ShapeDtypeStruct((b, 1, hq, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, hkv, d), jnp.bfloat16, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
    assert "tpu_custom_call" in _compile(da.decode_attention, q, kv, kv,
                                         lengths)


def test_ssd_scan_compiles_at_mamba2_width(one_chip):
    s = MAMBA.ssm
    b, t = 1, 512
    nh, hd, n = s.n_heads(MAMBA.d_model), s.head_dim, s.d_state
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)
    text = _compile(lambda x, dt, A, B, C, D: ssd_scan.ssd(
        x, dt, A, B, C, D, chunk=s.chunk),
        f32(b, t, nh, hd), f32(b, t, nh), f32(nh), f32(b, t, n),
        f32(b, t, n), f32(nh))
    assert "tpu_custom_call" in text


def test_flash_attention_gradient_compiles_on_a_2x2_mesh(v5e, monkeypatch):
    """A training step on a mesh differentiates the kernel and partitions
    it, neither of which Pallas does alone; ``ops`` adds both.  ``ops``
    sees this process's CPU, so the test steers it."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = Mesh(np.array(v5e.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    rows = NamedSharding(mesh, P("data"))
    t, hq, hkv, d = 512, GEMMA.n_heads, GEMMA.n_kv_heads, GEMMA.hd
    q = jax.ShapeDtypeStruct((8, t, hq, d), jnp.float32, sharding=rows)
    kv = jax.ShapeDtypeStruct((8, t, hkv, d), jnp.float32, sharding=rows)
    loss = lambda q, k, v: ops.flash_attention(q, k, v).sum()
    with shard_ctx.plan_specs(P("data", None, None), None, mesh=mesh):
        text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), q, kv,
                        kv)
    assert "tpu_custom_call" in text


def test_gemma_decode_step_compiles_with_pallas(one_chip, monkeypatch):
    """The whole full-width decode step, as the engine jits it (bf16
    weights, max_batch 8, max_len 1024), fits one chip with the platform's
    lowering; ``ops`` sees this process's CPU, so the test steers it."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    model = build_model(GEMMA)
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    params = on_chip(model.param_specs(jnp.bfloat16))
    cache = on_chip(model.init_cache(8, 1024, abstract=True))
    batch = on_chip({"tokens": jax.ShapeDtypeStruct((8, 1), jnp.int32),
                     "lengths": jax.ShapeDtypeStruct((8,), jnp.int32)})
    compiled = jax.jit(lambda p, c, b: model.apply_decode(p, c, b),
                       donate_argnums=(1,)).lower(params, cache,
                                                  batch).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    resident = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert resident < 16 * 2 ** 30


def test_mamba2_decode_step_updates_the_state_in_place(one_chip,
                                                       monkeypatch):
    """The full-width state-space decode step at the benchmark's geometry
    (bf16 weights, 32 slots of 1024) updates the stacked recurrent state
    in the donated cache: no copy of the whole ``h`` or ``conv`` stack, no
    temporary as large as ``h``, and no kernel (the step is plain XLA).
    ``ops`` sees this process's CPU, so the test steers it."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    model = build_model(MAMBA)
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    params = on_chip(model.param_specs(jnp.bfloat16))
    cache = on_chip(model.init_cache(32, 1024, abstract=True))
    batch = on_chip({"tokens": jax.ShapeDtypeStruct((32, 1), jnp.int32),
                     "lengths": jax.ShapeDtypeStruct((32,), jnp.int32)})
    compiled = jax.jit(lambda p, c, b: model.apply_decode(p, c, b),
                       donate_argnums=(1,)).lower(params, cache,
                                                  batch).compile()
    text = compiled.as_text()
    for k in ("h", "conv"):
        dims = ",".join(map(str, cache[k].shape))
        assert not re.findall(rf"%\S*copy\S* = \w+\[{dims}\]", text), k
    h_bytes = cache["h"].size * cache["h"].dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < h_bytes
    assert "tpu_custom_call" not in text
