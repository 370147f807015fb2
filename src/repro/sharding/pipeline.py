"""GPipe-style pipeline parallelism over the ``pod`` axis — the TPU rendering
of HiDP's *global model partitioning* (layer blocks pipelined across nodes,
§II-A "inherently temporal").

Implementation: ``shard_map`` over ``pod``; each pod holds a contiguous layer
stage (stacked params reshaped (S, L/S, ...) and sharded on the stage dim).
Microbatches stream through a scan of M + S − 1 ticks; activations hop stages
with ``ppermute``; the last stage's outputs are zero-masked and ``psum``-ed
back to all pods.  Reverse-mode AD through scan+ppermute yields the standard
GPipe forward-then-backward schedule; the bubble fraction (S−1)/(M+S−1) is
what the HiDP global DP weighs against data partitioning's gradient
all-reduce over DCN.

Used for train/prefill shapes when the tier-1 DP picks model mode (forced
via ``dryrun.py --force-global model``), and exercised by
tests/test_pipeline.py on a CPU mesh.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import layers as L
from repro.models import transformer
from repro.models.config import ArchConfig


def stage_params(cfg: ArchConfig, params: dict, n_stages: int) -> dict:
    """Reshape the stacked layer params (L, ...) → (S, L/S, ...)."""
    if cfg.n_layers % n_stages:
        raise ValueError(f"{cfg.n_layers} layers not divisible into "
                         f"{n_stages} stages")
    per = cfg.n_layers // n_stages
    out = dict(params)
    out["layers"] = jax.tree.map(
        lambda a: a.reshape((n_stages, per) + tuple(a.shape[1:])),
        params["layers"])
    return out


def stage_param_shardings(mesh: Mesh, params_staged: dict, axis: str = "pod"
                          ) -> dict:
    """Stage dim over `axis`, everything else replicated (pipeline keeps
    weights stage-resident; intra-stage TP can compose via the layer rules
    but is kept off in this reference implementation)."""
    def leaf_sh(path, leaf):
        names = [str(k.key) for k in path
                 if isinstance(k, jax.tree_util.DictKey)]
        if names and names[0] == "layers":
            return NamedSharding(mesh, P(axis))
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map_with_path(leaf_sh, params_staged)


def pipeline_hidden(cfg: ArchConfig, params_staged: dict, tokens: jax.Array,
                    *, mesh: Mesh, n_stages: int, microbatches: int,
                    axis: str = "pod") -> jax.Array:
    """Forward through the pipelined stack.  tokens: (B, T) int32.
    Returns final-normed hidden states (B, T, d), replicated over `axis`.
    """
    B, T = tokens.shape
    M = microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible into {M} microbatches")
    mb = B // M
    tokens_m = tokens.reshape(M, mb, T)

    layer_leaves = params_staged["layers"]
    embed_p = params_staged["embed"]
    norm_p = params_staged["final_norm"]

    def local(layers_stage, embed_local, norm_local, toks):
        # layers_stage leaves: (1, L/S, ...) → (L/S, ...)
        layers_stage = jax.tree.map(lambda a: a[0], layers_stage)
        stage = jax.lax.axis_index(axis)
        positions = jnp.broadcast_to(jnp.arange(T)[None], (mb, T))

        def run_stage(x):
            def body(c, p):
                y, _ = transformer.apply_layer(
                    cfg, p, c, mode="train", positions=positions,
                    window=None, layer_cache=None, lengths=None)
                return y, None
            y, _ = jax.lax.scan(body, x, layers_stage)
            return y

        d = cfg.d_model
        zero = jnp.zeros((mb, T, d), jnp.bfloat16)
        outs0 = jnp.zeros((M, mb, T, d), jnp.bfloat16)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            buf, outs = carry
            mb_idx = jnp.clip(t, 0, M - 1)
            emb = L.embed(embed_local, tokens_m_local[mb_idx]
                          ).astype(jnp.bfloat16)
            x_in = jnp.where(stage == 0, emb, buf)
            y = run_stage(x_in)
            # last stage finished microbatch (t − S + 1)
            out_idx = t - (n_stages - 1)
            is_out = (stage == n_stages - 1) & (out_idx >= 0)
            outs = jax.lax.cond(
                is_out,
                lambda o: o.at[jnp.clip(out_idx, 0, M - 1)].set(y),
                lambda o: o, outs)
            y_next = jax.lax.ppermute(y, axis, perm)
            return (y_next, outs), None

        tokens_m_local = toks                       # (M, mb, T) replicated
        (buf, outs), _ = jax.lax.scan(
            tick, (zero, outs0), jnp.arange(M + n_stages - 1))
        # only the last stage holds real outputs — psum the masked stack
        outs = jnp.where(stage == n_stages - 1, outs, 0)
        outs = jax.lax.psum(outs, axis)
        x = outs.reshape(B, T, d)
        return L.apply_norm(cfg, norm_local, x)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), layer_leaves),
                  jax.tree.map(lambda _: P(), embed_p),
                  jax.tree.map(lambda _: P(), norm_p),
                  P()),
        out_specs=P(),
        check_vma=False)
    return fn(layer_leaves, embed_p, norm_p, tokens_m)


def make_pipeline_train_step(model, opt_cfg, plan, mesh):
    """Pipeline-parallel training step (CE loss over the pipelined hidden).

    Composes with the data-parallel axes only through the batch dimension
    staying un-sharded here (reference implementation, stage-resident
    weights); the HiDP planner prices this against data mode via the bubble
    term."""
    from repro.training import optimizer as optim
    from repro.training.train_loop import chunked_ce_loss

    cfg = model.cfg
    S = plan.pipeline_stages
    M = max(plan.microbatches, S)

    def loss_fn(params_staged, batch):
        hidden = pipeline_hidden(cfg, params_staged, batch["tokens"],
                                 mesh=mesh, n_stages=S, microbatches=M)
        return chunked_ce_loss(model, params_staged, hidden,
                               batch["targets"], chunks=8)

    def train_step(params_staged, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params_staged, batch)
        params_staged, opt_state, metrics = optim.apply_updates(
            opt_cfg, params_staged, grads, opt_state)
        metrics["loss"] = loss
        return params_staged, opt_state, metrics

    return train_step
