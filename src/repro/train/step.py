"""Jittable train / serve steps and their sharding trees.

``make_train_step(model, opt_cfg)`` returns ``step(state, batch)`` where
``state = {"params", "opt": {m, v, master, count}, "step"}``.

``state_shardings`` builds the NamedSharding tree: params follow the
model's logical axes; optimizer state follows the ZeRO-rewritten axes
(additionally sharded over the data axes); scalars are replicated.

Optional gradient accumulation runs microbatches under ``jax.lax.scan``
(grads averaged in fp32), trading activation memory for step latency.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import sharding as shd
from repro.distributed import zero as zero_lib
from repro.train import optimizer as opt_lib


def make_train_step(model, opt_cfg: opt_lib.OptConfig, accum: int = 1):
    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def compute_grads(params, batch):
        if accum <= 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return loss, metrics, grads
        split = lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:])
        micro = jax.tree.map(split, batch)

        def body(carry, mb):
            acc, _ = carry
            (loss, metrics), grads = grad_fn(params, mb)
            acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / accum, acc, grads)
            return (acc, loss), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss), _ = jax.lax.scan(body, (zeros, jnp.zeros((), jnp.float32)),
                                        micro)
        return loss, {"loss": loss}, grads

    def train_step(state: Dict[str, Any], batch: Dict[str, jax.Array]):
        loss, metrics, grads = compute_grads(state["params"], batch)
        with jax.named_scope("optimizer"):
            new_params, new_opt, opt_metrics = opt_lib.apply_updates(
                opt_cfg, state["params"], grads, state["opt"])
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        return new_state, metrics

    return train_step


def init_state(model, opt_cfg: opt_lib.OptConfig, key):
    params = model.init(key)
    return {"params": params,
            "opt": opt_lib.init_opt_state(opt_cfg, params),
            "step": jnp.zeros((), jnp.int32)}


def state_shapes(model, opt_cfg: opt_lib.OptConfig):
    params = model.init_shape()
    return {"params": params,
            "opt": opt_lib.opt_state_shapes(opt_cfg, params),
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def state_shardings(model, opt_cfg: opt_lib.OptConfig, mesh: Mesh,
                    rules: Optional[shd.ShardingRules] = None,
                    zero: bool = True, scheme: str = "sp",
                    fsdp_params: bool = False):
    rules = shd.scheme_rules(scheme, rules)
    axes = model.param_axes()
    shapes = model.init_shape()
    if scheme == "dp":
        axes = shd.fsdp_axes(axes, shapes, mesh)
    if fsdp_params:  # giant models: params also sharded over (pod, data)
        axes = zero_lib.zero_axes(axes, shapes, mesh, rules)
        rules = zero_lib.zero_rules(rules)
    p_sh = shd.tree_shardings(mesh, axes, shapes, rules)
    if zero:
        zrules = zero_lib.zero_rules(rules)
        zaxes = zero_lib.zero_axes(axes, shapes, mesh, rules)
        z_sh = shd.tree_shardings(mesh, zaxes, shapes, zrules)
    else:
        z_sh = p_sh
    repl = NamedSharding(mesh, P())
    master = (jax.tree.map(lambda x: x, z_sh)
              if _has_master(model, opt_cfg) else {})
    return {
        "params": p_sh,
        "opt": {"m": z_sh, "v": z_sh, "count": repl, "master": master},
        "step": repl,
    }


def _has_master(model, opt_cfg) -> bool:
    return opt_cfg.keep_master and model.dtype != jnp.float32


def batch_shardings(mesh: Mesh, batch_shapes,
                    rules: Optional[shd.ShardingRules] = None):
    rules = rules or shd.ShardingRules()

    def leaf(sds):
        axes = ("batch",) + (None,) * (len(sds.shape) - 1)
        return NamedSharding(mesh, shd.resolve_spec(axes, sds.shape, mesh, rules))

    return jax.tree.map(leaf, batch_shapes)


def metric_shardings(mesh: Mesh, metrics_shape):
    repl = NamedSharding(mesh, P())
    return jax.tree.map(lambda _: repl, metrics_shape)
