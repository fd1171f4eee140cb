"""Builders for the jit-lowered step functions (train / prefill / decode /
cross-pod FL round) with full sharding annotations.

These are used identically by the real trainer (``launch/train.py``), the
multi-pod dry-run (``launch/dryrun.py``) and the roofline analysis.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import MeshConfig, ModelConfig, ShapeConfig, TrainConfig
from repro.models import build_model
from repro.models.layers import abstract_init
from repro.optim.optimizers import (OptState, adamw_init, adamw_update,
                                    opt_state_axes)
from repro.optim.schedules import cosine_warmup
from repro.sharding.rules import MeshPlan, Sharder


@dataclasses.dataclass
class StepBundle:
    """Everything needed to lower one cell."""
    fn: object  # jit-able callable
    in_specs: tuple  # ShapeDtypeStructs pytree(s) for .lower()
    in_shardings: tuple
    out_shardings: object
    model: object
    plan: MeshPlan
    abstract_state: object  # params/opt/cache shape pytrees (for reports)
    exchange_bytes: int = 0  # fl_round: what one pod sends per round


def _shardings(mesh, plan: MeshPlan, axes_tree, shapes_tree):
    return plan.tree_shardings(mesh, axes_tree, shapes_tree)


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, shape: ShapeConfig, mesh,
                    mesh_cfg: MeshConfig, train_cfg: TrainConfig,
                    *, fl_pods: bool = False):
    """Synchronous data/tensor-parallel train step (one optimizer update).

    ``fl_pods=False``: batch sharded over (pod, data); params FSDP over
    fsdp_axes, TP over model — the standard fully-synchronous baseline.
    """
    plan = MeshPlan(mesh_cfg)
    sharder = Sharder(plan, mesh)
    model = build_model(cfg, sharder)
    p_shapes, p_axes = abstract_init(model.init)
    opt_shapes = jax.eval_shape(lambda p: adamw_init(p, train_cfg), p_shapes)
    o_axes = opt_state_axes(p_axes, train_cfg)

    in_specs, in_axes = model.input_specs(shape)

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch)
        return loss, metrics

    def train_step(params, opt_state, batch, step):
        if train_cfg.microbatches > 1:
            n = train_cfg.microbatches

            def micro(carry, mb):
                gsum, lsum = carry
                (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
                gsum = jax.tree.map(jnp.add, gsum, g)
                return (gsum, lsum + l), None

            mb_batch = jax.tree.map(
                lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch)
            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params)
            (gsum, lsum), _ = jax.lax.scan(micro, (zeros, 0.0), mb_batch)
            grads = jax.tree.map(lambda g: (g / n).astype(jnp.bfloat16), gsum)
            loss = lsum / n
        else:
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch)
        lr = cosine_warmup(step, base_lr=train_cfg.learning_rate,
                           warmup_steps=train_cfg.warmup_steps,
                           total_steps=train_cfg.total_steps)
        new_params, new_opt, gnorm = adamw_update(grads, opt_state, params,
                                                  lr, train_cfg)
        return new_params, new_opt, {"loss": loss, "gnorm": gnorm, "lr": lr}

    p_shard = _shardings(mesh, plan, p_axes, p_shapes)
    o_shard = _shardings(mesh, plan, o_axes, opt_shapes)
    b_shard = _shardings(mesh, plan, in_axes, in_specs)
    step_shard = NamedSharding(mesh, P())
    in_shardings = (p_shard, o_shard, b_shard, step_shard)
    out_shardings = (p_shard, o_shard,
                     {"loss": step_shard, "gnorm": step_shard,
                      "lr": step_shard})
    lower_args = (p_shapes, opt_shapes, in_specs,
                  jax.ShapeDtypeStruct((), jnp.int32))
    return StepBundle(train_step, lower_args, in_shardings, out_shardings,
                      model, plan, {"params": p_shapes, "opt": opt_shapes})


# ---------------------------------------------------------------------------
# serve steps (prefill forward / single-token decode)
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig, shape: ShapeConfig, mesh,
                      mesh_cfg: MeshConfig):
    plan = MeshPlan(mesh_cfg)
    sharder = Sharder(plan, mesh)
    model = build_model(cfg, sharder)
    p_shapes, p_axes = abstract_init(model.init)
    in_specs, in_axes = model.input_specs(shape)

    def prefill_step(params, batch):
        logits, _ = model.forward(params, batch)
        # serving returns only the last-position logits
        return logits[:, -1]

    p_shard = _shardings(mesh, plan, p_axes, p_shapes)
    b_shard = _shardings(mesh, plan, in_axes, in_specs)
    out_sh = NamedSharding(mesh, plan.spec(
        ("batch", "vocab"), (shape.global_batch, cfg.vocab_size)))
    return StepBundle(prefill_step, (p_shapes, in_specs),
                      (p_shard, b_shard), out_sh, model, plan,
                      {"params": p_shapes})


def make_decode_step(cfg: ModelConfig, shape: ShapeConfig, mesh,
                     mesh_cfg: MeshConfig):
    """One new token against a seq_len KV cache (decode_* cells)."""
    plan = MeshPlan(mesh_cfg)
    sharder = Sharder(plan, mesh)
    model = build_model(cfg, sharder)
    p_shapes, p_axes = abstract_init(model.init)
    in_specs, in_axes = model.input_specs(shape)
    cache_spec, cache_axes = model.cache_spec(shape.global_batch,
                                              shape.seq_len)

    def decode_step(params, cache, batch):
        logits, new_cache = model.decode_step(params, cache, batch)
        return logits, new_cache

    p_shard = _shardings(mesh, plan, p_axes, p_shapes)
    c_shard = _shardings(mesh, plan, cache_axes, cache_spec)
    b_shard = _shardings(mesh, plan, in_axes, in_specs)
    logit_sh = NamedSharding(mesh, plan.spec(
        ("batch", None, "vocab"), (shape.global_batch, 1, cfg.vocab_size)))
    return StepBundle(decode_step, (p_shapes, cache_spec, in_specs),
                      (p_shard, c_shard, b_shard), (logit_sh, c_shard),
                      model, plan, {"params": p_shapes, "cache": cache_spec})


# ---------------------------------------------------------------------------
# cross-pod FL round (the paper's technique at pod scale)
# ---------------------------------------------------------------------------

def make_fl_round_step(cfg: ModelConfig, shape: ShapeConfig, mesh,
                       mesh_cfg: MeshConfig, train_cfg: TrainConfig,
                       *, local_steps: int = 4):
    """DiLoCo-style: each pod trains ``local_steps`` on its own batch
    (params stacked over the pod axis -> vmap = per-pod divergence), then
    pods exchange int8-quantised deltas (cross-pod all-reduce carries
    1-byte traffic + per-block scales instead of f32). Requires the
    multi-pod mesh."""
    assert "pod" in mesh_cfg.axis_names, "fl round needs the pod axis"
    n_pods = mesh_cfg.axis_size("pod")
    # per-pod plan: batch maps to 'data' only (pod handled by stacking)
    pod_mesh_cfg = dataclasses.replace(mesh_cfg, batch_axes=("data",))
    plan = MeshPlan(pod_mesh_cfg)
    sharder = Sharder(plan, mesh)
    model = build_model(cfg, sharder)
    p_shapes, p_axes = abstract_init(model.init)
    opt_shapes = jax.eval_shape(lambda p: adamw_init(p, train_cfg), p_shapes)
    o_axes = opt_state_axes(p_axes, train_cfg)
    in_specs, in_axes = model.input_specs(shape)

    # stack over pods: leading 'pod' logical axis
    stack = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_pods,) + s.shape, s.dtype), tree)
    stack_axes = lambda tree: jax.tree.map(
        lambda a: ("pod_stack",) + tuple(a or ()), tree,
        is_leaf=lambda x: x is None or (isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x)))
    plan_stacked = MeshPlan(pod_mesh_cfg,
                            extra_rules=(("pod_stack", ("pod",)),))

    ps_shapes, ps_axes = stack(p_shapes), stack_axes(p_axes)
    os_shapes, os_axes = stack(opt_shapes), stack_axes(o_axes)
    # per-pod batch: local batch = global/n_pods, stacked over pods
    bs_specs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            (n_pods, local_steps, s.shape[0] // n_pods) + s.shape[1:],
            s.dtype), in_specs)
    bs_axes = jax.tree.map(
        lambda a: ("pod_stack", None) + tuple(a or ()), in_axes,
        is_leaf=lambda x: x is None or (isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x)))

    def local_steps_fn(params, opt_state, batches, step):
        def one(carry, xs):
            p, o = carry
            mb, i = xs
            (loss, _), g = jax.value_and_grad(
                lambda pp: model.loss(pp, mb), has_aux=True)(p)
            lr = cosine_warmup(step + i, base_lr=train_cfg.learning_rate,
                               warmup_steps=train_cfg.warmup_steps,
                               total_steps=train_cfg.total_steps)
            p, o, _ = adamw_update(g, o, p, lr, train_cfg)
            return (p, o), loss

        (params, opt_state), losses = jax.lax.scan(
            one, (params, opt_state), (batches, jnp.arange(local_steps)))
        return params, opt_state, losses.mean()

    def fl_round(params_stacked, opt_stacked, anchor, batches, step):
        new_p, new_o, loss = jax.vmap(local_steps_fn,
                                      in_axes=(0, 0, 0, None))(
            params_stacked, opt_stacked, batches, step)
        def sync(anchor_leaf, stacked_leaf, axes_leaf):
            delta = (stacked_leaf.astype(jnp.float32)
                     - anchor_leaf.astype(jnp.float32)[None])
            base = plan.spec(tuple(axes_leaf or ()),
                             tuple(anchor_leaf.shape))
            replicated = NamedSharding(mesh, P(*((None,) + tuple(base))))
            mean = exchange_delta(
                delta, compression=train_cfg.crosspod_compression,
                n_pods=n_pods,
                gather=lambda q: jax.lax.with_sharding_constraint(
                    q, replicated))
            new_anchor = anchor_leaf.astype(jnp.float32) + mean
            return new_anchor.astype(anchor_leaf.dtype)

        a_leaves, treedef = jax.tree.flatten(anchor)
        s_leaves = treedef.flatten_up_to(new_p)
        ax_leaves = jax.tree.leaves(
            p_axes, is_leaf=lambda x: x is None or (
                isinstance(x, tuple) and all(
                    isinstance(e, (str, type(None))) for e in x)))
        new_anchor = jax.tree.unflatten(
            treedef, [sync(a, s, ax) for a, s, ax
                      in zip(a_leaves, s_leaves, ax_leaves)])
        # reset every pod to the new anchor
        reset = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_pods,) + a.shape),
            new_anchor)
        return reset, new_o, new_anchor, loss.mean()

    ps_shard = plan_stacked.tree_shardings(mesh, ps_axes, ps_shapes)
    os_shard = plan_stacked.tree_shardings(mesh, os_axes, os_shapes)
    a_shard = plan_stacked.tree_shardings(mesh, p_axes, p_shapes)
    b_shard = plan_stacked.tree_shardings(mesh, bs_axes, bs_specs)
    step_sh = NamedSharding(mesh, P())
    in_shardings = (ps_shard, os_shard, a_shard, b_shard, step_sh)
    out_shardings = (ps_shard, os_shard, a_shard, step_sh)
    lower_args = (ps_shapes, os_shapes, p_shapes, bs_specs,
                  jax.ShapeDtypeStruct((), jnp.int32))
    sizes = [math.prod(s.shape) for s in jax.tree.leaves(p_shapes)]
    if train_cfg.crosspod_compression == "int8":
        sent = sum(sizes) + 4 * len(sizes)  # int8 codes + an f32 max each
    else:
        sent = 4 * sum(sizes)
    return StepBundle(fl_round, lower_args, in_shardings, out_shardings,
                      model, plan_stacked,
                      {"params": ps_shapes, "opt": os_shapes},
                      exchange_bytes=sent)


def exchange_delta(delta, *, compression: str, n_pods: int, gather):
    """The pods' mean of a leaf's ``(n_pods, ...)`` float32 delta, as the
    cross-pod exchange carries it.

    'int8': one scale shared by the pods, ``max|delta| / 127``; each pod's
    delta rounded to int8 codes, which are replicated across the pod axis
    (``gather``: an all-gather of int8) and summed locally in int32.
    Summing before the collective would silently promote the wire traffic
    to 4-byte ints. Otherwise the float32 mean."""
    if compression != "int8":
        return jnp.mean(delta, axis=0)
    scale = jnp.max(jnp.abs(delta)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(delta / scale), -127, 127).astype(jnp.int8)
    # the barrier pins q as a materialised pod-sharded int8 tensor BEFORE
    # the resharding constraint; without it the partitioner all-gathers
    # the f32 delta and requantises per pod (4x the payload, observed in
    # the lowered HLO)
    q = gather(jax.lax.optimization_barrier(q))
    total = jnp.sum(q.astype(jnp.int32), axis=0).astype(jnp.float32)
    return total * scale / n_pods


def bundle_for(kind: str, cfg: ModelConfig, shape: ShapeConfig, mesh,
               mesh_cfg: MeshConfig, train_cfg: Optional[TrainConfig] = None,
               **kw):
    train_cfg = train_cfg or TrainConfig()
    if kind == "train":
        return make_train_step(cfg, shape, mesh, mesh_cfg, train_cfg, **kw)
    if kind == "prefill":
        return make_prefill_step(cfg, shape, mesh, mesh_cfg)
    if kind == "decode":
        return make_decode_step(cfg, shape, mesh, mesh_cfg)
    if kind == "fl_round":
        return make_fl_round_step(cfg, shape, mesh, mesh_cfg, train_cfg, **kw)
    raise ValueError(kind)
