"""Expert-parallel MoE via shard_map + all_to_all + sort-based ragged matmul.

This is the HiDP local partitioner's "expert partitioning" sub-mode — the
beyond-P1 lowering that replaces the dense all-expert einsum (layers.moe_dense,
which burns num_experts/top_k× the useful FLOPs) with:

  1. per-chip routing (top-k over a replicated router),
  2. capacity-bounded all_to_all over the EP axis to the chips owning each
     expert (dispatch buffer: (ep, capacity, d)),
  3. sort-by-expert + ``jax.lax.ragged_dot`` grouped matmuls on each chip —
     executed FLOPs ≈ active FLOPs (modulo capacity padding),
  4. all_to_all back + weighted combine at the source chip.

Tokens over capacity are dropped (classic Switch semantics, capacity_factor
1.25 by default); correctness tests compare against moe_dense with a large
capacity factor so nothing drops.

The mesh and EP axis arrive via repro.sharding.ctx (published by the
launcher); without a published mesh the caller should use moe_dense.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import ctx as shard_ctx

from .config import ArchConfig


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _quant_i8(v: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-row symmetric int8 quantisation (rows = tokens)."""
    scale = jnp.max(jnp.abs(v.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(v.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _a2a_i8(v: jax.Array, axis: str) -> jax.Array:
    """all_to_all whose payload crosses the wire in int8 (+fp32 row scales);
    straight-through gradients, themselves int8-quantised on the reverse
    a2a (error stays bounded by the per-row scale)."""
    q, s = _quant_i8(v)
    rq = jax.lax.all_to_all(q, axis, 0, 0, tiled=False)
    rs = jax.lax.all_to_all(s, axis, 0, 0, tiled=False)
    return (rq.astype(jnp.float32) * rs).astype(v.dtype)


def _a2a_i8_fwd(v, axis):
    return _a2a_i8(v, axis), None


def _a2a_i8_bwd(axis, _, g):
    q, s = _quant_i8(g)
    rq = jax.lax.all_to_all(q, axis, 0, 0, tiled=False)
    rs = jax.lax.all_to_all(s, axis, 0, 0, tiled=False)
    return ((rq.astype(jnp.float32) * rs).astype(g.dtype),)


_a2a_i8.defvjp(_a2a_i8_fwd, _a2a_i8_bwd)


def moe_ep_a2a(cfg: ArchConfig, p: dict, x: jax.Array, *,
               axis: str | None = None,
               capacity_factor: float | None = None,
               a2a_dtype: str = "bfloat16") -> jax.Array:
    """x: (B, T, d) — batch/seq sharded per the activation spec, replicated
    over the EP axis.  p: one layer's MoE params (expert dim sharded over the
    EP axis).  Returns (B, T, d) like moe_dense."""
    mesh = shard_ctx.get_mesh()
    if mesh is None:
        from . import layers as L
        return L.moe_dense(cfg, p, x)
    ep_axis = axis or shard_ctx.get_ep_axis() or "model"
    act_spec = shard_ctx.get_act_spec() or P()
    spec = cfg.moe
    cf = capacity_factor or spec.capacity_factor
    ep = mesh.shape[ep_axis] if isinstance(ep_axis, str) else 1
    E = spec.num_experts
    if E % ep == 0:
        replicas = 1
        e_loc = E // ep
    elif ep % E == 0:
        # fewer experts than EP ranks (mixtral 8e over a 16-wide axis):
        # replicate each expert over r ranks and load-balance tokens across
        # replicas; the replicated weight view is a transient gather that
        # shards to one expert per chip (no per-chip memory waste).
        replicas = ep // E
        e_loc = 1
    else:
        from . import layers as L
        return L.moe_dense(cfg, p, x)

    # every rank must own an equal token slice — unless the seq dim is
    # already sharded over the EP axis (sequence-parallel layouts)
    total_tokens = x.shape[0] * x.shape[1]
    bsz_chk = dict(zip(mesh.axis_names, mesh.devices.shape))

    def _shard_chk(nm):
        if nm is None:
            return 1
        if isinstance(nm, tuple):
            o = 1
            for a in nm:
                o *= bsz_chk[a]
            return o
        return bsz_chk[nm]
    act_spec_chk = shard_ctx.get_act_spec() or P()
    seq_e = act_spec_chk[1] if len(act_spec_chk) > 1 else None
    seq_set = (set(seq_e) if isinstance(seq_e, tuple)
               else {seq_e} if seq_e else set())
    if ep_axis not in seq_set:
        div = 1
        for i in range(min(len(act_spec_chk), 2)):
            div *= _shard_chk(act_spec_chk[i])
        if (total_tokens // max(div, 1)) % ep:
            from . import layers as L
            return L.moe_dense(cfg, p, x)

    in_specs = (
        P(*act_spec),                           # x
        P(),                                    # router (replicated)
        P(ep_axis, None, None),                 # w_gate (E·r, d, ffe)
        P(ep_axis, None, None),                 # w_up
        P(ep_axis, None, None),                 # w_down
    )

    # when the activation seq dim is already sharded over the EP axis
    # (sequence-parallel layouts), each rank's block IS its token slice:
    # no slicing on entry and no all-gather on exit.
    seq_entry = act_spec[1] if len(act_spec) > 1 else None
    seq_axes_set = (set(seq_entry) if isinstance(seq_entry, tuple)
                    else {seq_entry} if seq_entry else set())
    tokens_pre_sharded = ep_axis in seq_axes_set

    def local(xb, router, w_gate, w_up, w_down):
        bl, tl, d = xb.shape
        t_full = bl * tl
        if tokens_pre_sharded:
            t = t_full
            x2 = xb.reshape(t, d)
        else:
            # activations are replicated over the EP axis — each rank owns a
            # 1/ep token slice (otherwise every rank would dispatch the same
            # assignments and the expert compute would duplicate ep×)
            t = t_full // ep
            rank = jax.lax.axis_index(ep_axis)
            x2 = jax.lax.dynamic_slice_in_dim(
                xb.reshape(t_full, d), rank * t, t, axis=0)
        cap = _round_up(max(int(t * spec.top_k * cf / ep), 8), 8)
        # 1. routing (fp32)
        logits = x2.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        vals, idx = jax.lax.top_k(probs, spec.top_k)           # (t, k)
        vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
        flat_e = idx.reshape(-1)                               # (t*k,)
        flat_w = vals.reshape(-1)
        flat_tok = jnp.arange(t * spec.top_k) // spec.top_k
        if replicas == 1:
            dest = flat_e // e_loc                             # (t*k,)
            local_e = flat_e % e_loc
        else:
            dest = flat_e * replicas + (flat_tok % replicas)
            local_e = jnp.zeros_like(flat_e)
        # 2. capacity-bounded dispatch buffers
        onehot_dest = jax.nn.one_hot(dest, ep, dtype=jnp.int32)
        pos = jnp.cumsum(onehot_dest, axis=0) - onehot_dest    # pos within dest
        pos = (pos * onehot_dest).sum(-1)                      # (t*k,)
        keep = pos < cap
        send_x = jnp.zeros((ep, cap, d), xb.dtype)
        send_x = send_x.at[dest, pos].set(
            jnp.where(keep[:, None], x2[flat_tok], 0.0), mode="drop")
        send_el = jnp.zeros((ep, cap), jnp.int32)
        send_el = send_el.at[dest, pos].set(
            jnp.where(keep, local_e, 0), mode="drop")
        # 3. a2a to expert owners (optionally int8-quantised: the dispatch
        # payload is the dominant collective of EP training — §Perf A3)
        if a2a_dtype == "int8":
            recv_x = _a2a_i8(send_x, ep_axis)
        else:
            recv_x = jax.lax.all_to_all(send_x, ep_axis, 0, 0, tiled=False)
        recv_el = jax.lax.all_to_all(send_el[..., None], ep_axis, 0, 0,
                                     tiled=False)[..., 0]
        n = ep * cap
        rx = recv_x.reshape(n, d)
        rel = recv_el.reshape(n)
        # 4. sort by local expert, ragged grouped matmul, unsort
        order = jnp.argsort(rel)
        inv = jnp.argsort(order)
        xs = rx[order].astype(jnp.bfloat16)
        gs = jnp.bincount(rel, length=e_loc).astype(jnp.int32)
        gate = jax.lax.ragged_dot(xs, w_gate.astype(jnp.bfloat16), gs)
        up = jax.lax.ragged_dot(xs, w_up.astype(jnp.bfloat16), gs)
        h = (jax.nn.silu(gate.astype(jnp.float32)).astype(jnp.bfloat16)
             * up)
        out = jax.lax.ragged_dot(h, w_down.astype(jnp.bfloat16), gs)
        out = out[inv].reshape(ep, cap, d)
        # 5. a2a back + weighted combine at source
        if a2a_dtype == "int8":
            back = _a2a_i8(out.astype(jnp.float32), ep_axis)
        else:
            back = jax.lax.all_to_all(out, ep_axis, 0, 0, tiled=False)
        contrib = back[dest, pos].astype(jnp.float32)          # (t*k, d)
        contrib *= (flat_w * keep)[:, None]
        y = jnp.zeros((t, d), jnp.float32).at[flat_tok].add(contrib)
        if tokens_pre_sharded:
            return y.astype(xb.dtype).reshape(bl, tl, d)
        # restore replication over the EP axis (each rank computed its slice)
        y = jax.lax.all_gather(y.astype(xb.dtype), ep_axis, axis=0,
                               tiled=True)
        return y.reshape(bl, tl, d)

    w_gate, w_up, w_down = p["w_gate"], p["w_up"], p["w_down"]
    if replicas > 1:
        # transient replicated-expert view; shards to 1 expert per chip
        w_gate = jnp.repeat(w_gate, replicas, axis=0)
        w_up = jnp.repeat(w_up, replicas, axis=0)
        w_down = jnp.repeat(w_down, replicas, axis=0)
    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=P(*act_spec), check_vma=False)
    return fn(x, p["router"], w_gate, w_up, w_down)
