"""Train step: loss -> grads -> WANify cross-pod sync -> AdamW.

Two composition modes:
  * single-pod mesh ("data","model"): a plain pjit step; XLA owns all
    collectives (FSDP/TP from sharding constraints).
  * multi-pod mesh ("pod","data","model"): the WHOLE step runs inside
    shard_map with ONLY the pod axis manual — per-pod gradients are
    synchronized by wan_allreduce (the paper's technique; baseline
    psum_allreduce selectable), then the optimizer update is applied
    identically on every pod (params stay pod-replicated).

Optional microbatching (gradient accumulation) shrinks activation
memory; optional wire compression (SAGQ analogue) rides the WAN hop.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.plan import WanPlan
from repro.core.wansync import psum_allreduce, wan_allreduce
from repro.models import registry
from repro.models.layers import ShardCtx
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state


def _grads_of(cfg: ModelConfig, ctx: ShardCtx, dp_size: int, microbatch: int,
              accum_dtype=jnp.float32):
    loss_f = registry.loss_fn(cfg, ctx, dp_size)

    def whole(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_f(p, batch), has_aux=True)(params)
        return loss, metrics, grads

    if microbatch <= 1:
        return whole

    def accumulated(params, batch):
        def split(x):
            b = x.shape[0]
            return x.reshape(microbatch, b // microbatch, *x.shape[1:])
        micro = jax.tree.map(split, batch)

        def body(carry, mb):
            loss_a, grads_a = carry
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: loss_f(p, mb), has_aux=True)(params)
            grads_a = jax.tree.map(
                lambda a, g: (a + g.astype(accum_dtype)).astype(accum_dtype),
                grads_a, grads)
            return (loss_a + loss, grads_a), metrics

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype), params)
        (loss, grads), metrics = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zeros), micro)
        grads = jax.tree.map(lambda g: g / microbatch, grads)
        metrics = jax.tree.map(lambda m: m[-1], metrics)
        return loss / microbatch, metrics, grads

    return accumulated


def make_train_step(cfg: ModelConfig, mesh, *, plan: Optional[WanPlan] = None,
                    opt: Optional[AdamWConfig] = None,
                    sync: str = "wanify",          # wanify | psum | none
                    compress: bool = False,
                    microbatch: int = 1,
                    accum_dtype=jnp.float32,
                    ctx: Optional[ShardCtx] = None) -> Callable:
    """Returns step(params, opt_state, batch) -> (params, opt_state, out)."""
    opt = opt or AdamWConfig()
    axes = mesh.axis_names
    multi_pod = "pod" in axes
    # inside the pod-manual shard_map only the auto axes may appear in
    # sharding constraints; the batch is already pod-local there.
    batch_axes = ("data",) if "data" in axes else ()
    dp_size = mesh.shape.get("data", 1)
    ctx = ctx or ShardCtx(batch_axes=batch_axes, model_axis="model"
                          if "model" in axes else None)
    grads_fn = _grads_of(cfg, ctx, dp_size, microbatch, accum_dtype)

    def core(params, opt_state, batch):
        loss, metrics, grads = grads_fn(params, batch)
        new_params, new_state, om = adamw_update(opt, params, grads, opt_state)
        out = {"loss": loss, **om,
               "ce": metrics.get("ce", loss),
               "expert_load": metrics.get("expert_load")}
        return new_params, new_state, out

    if not multi_pod:
        return core

    # ------------------------------------------------------------------
    # Multi-pod: vmap-over-pods formulation. Params / optimizer state /
    # batch carry an explicit leading pod dim sharded over "pod" (memory
    # per device identical to replication). Per-pod grads come from
    # vmapping the loss; the WANify schedule is jnp.roll over the pod dim
    # (lowers to collective-permute). The shard_map formulation
    # (wan_allreduce) emits the same wire pattern; kv_migrate uses it.
    # ------------------------------------------------------------------
    from repro.core.wansync import (psum_allreduce_batched,
                                    wan_allreduce_batched)
    n_pods = mesh.shape["pod"]

    def step(params_p, opt_state_p, batch):
        def split(x):
            return x.reshape(n_pods, x.shape[0] // n_pods, *x.shape[1:])
        batch_p = jax.tree.map(split, batch)

        def pod_grads(pp, bb):
            loss, metrics, grads = grads_fn(pp, bb)
            return loss, metrics, grads

        loss_p, metrics_p, grads_p = jax.vmap(pod_grads)(params_p, batch_p)
        loss = jnp.mean(loss_p)
        if sync == "wanify":
            assert plan is not None, "wanify sync needs a WanPlan"
            grads_p = wan_allreduce_batched(grads_p, plan, compress=compress)
        elif sync == "psum":
            grads_p = psum_allreduce_batched(grads_p, n_pods)
        new_params, new_state, om = jax.vmap(
            lambda p, g, s: adamw_update(opt, p, g, s)
        )(params_p, grads_p, opt_state_p)
        out = {"loss": loss,
               "grad_norm": jnp.mean(om["grad_norm"]),
               "lr": om["lr"][0],
               "ce": jnp.mean(metrics_p.get("ce", loss_p)),
               "expert_load": jnp.mean(metrics_p["expert_load"], axis=0)}
        return new_params, new_state, out

    return step


def broadcast_to_pods(tree: Any, n_pods: int) -> Any:
    """Add the explicit leading pod dim (replicated-in-value)."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_pods,) + x.shape), tree)


def strip_pods(tree: Any) -> Any:
    """Drop the pod dim (slices are value-identical after sync)."""
    return jax.tree.map(lambda x: x[0], tree)


def pod_specs(spec_tree: Any) -> Any:
    """Prepend the pod axis to every PartitionSpec."""
    return jax.tree.map(lambda s: P("pod", *s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def init_train_state(cfg: ModelConfig, key: jax.Array):
    params = registry.init_params(cfg, key)
    return params, init_opt_state(params)


def abstract_train_state(cfg: ModelConfig):
    return jax.eval_shape(lambda k: init_train_state(cfg, k),
                          jax.random.key(0))
