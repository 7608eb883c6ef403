"""AdamW with mixed-precision state, built for sharded pytrees.

Distributed-training memory tricks (all flag-controlled):
  - ``moment_dtype``: bf16 first/second moments (halves optimizer HBM);
  - ``keep_master``: fp32 master weights when compute params are bf16;
  - ZeRO-1 sharding of (m, v, master) is applied by the caller via
    ``repro.distributed.zero`` — this module is sharding-agnostic.

State layout (plain dict pytree, checkpoint-friendly):
  {"m": tree, "v": tree, "master": tree | None-like {}, "count": i32[]}
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    decay_steps: int = 10_000
    min_lr_ratio: float = 0.1
    moment_dtype: str = "float32"   # float32 | bfloat16
    keep_master: bool = True        # fp32 master when params are low-precision


def schedule(cfg: OptConfig, step):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(1.0, (step + 1) / max(1, cfg.warmup_steps))
    prog = jnp.clip((step - cfg.warmup_steps) /
                    max(1, cfg.decay_steps - cfg.warmup_steps), 0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return cfg.lr * warm * (cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * cos)


def _mdt(cfg: OptConfig):
    return jnp.bfloat16 if cfg.moment_dtype == "bfloat16" else jnp.float32


def init_opt_state(cfg: OptConfig, params) -> Any:
    mdt = _mdt(cfg)
    zeros = lambda p: jnp.zeros(p.shape, mdt)
    state = {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "count": jnp.zeros((), jnp.int32),
    }
    if cfg.keep_master and _needs_master(params):
        # a copy even of params that are already fp32: the train step
        # donates the whole state, and no buffer may be donated twice
        state["master"] = jax.tree.map(
            lambda p: jnp.array(p, jnp.float32, copy=True), params)
    else:
        state["master"] = {}
    return state


def opt_state_shapes(cfg: OptConfig, param_shapes) -> Any:
    """ShapeDtypeStruct tree of the optimizer state (for AOT lowering)."""
    mdt = _mdt(cfg)
    sds = lambda p, dt: jax.ShapeDtypeStruct(p.shape, dt)
    state = {
        "m": jax.tree.map(lambda p: sds(p, mdt), param_shapes),
        "v": jax.tree.map(lambda p: sds(p, mdt), param_shapes),
        "count": jax.ShapeDtypeStruct((), jnp.int32),
    }
    if cfg.keep_master and _needs_master(param_shapes):
        state["master"] = jax.tree.map(lambda p: sds(p, jnp.float32), param_shapes)
    else:
        state["master"] = {}
    return state


def _needs_master(params) -> bool:
    return any(leaf.dtype != jnp.float32 for leaf in jax.tree.leaves(params))


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def apply_updates(cfg: OptConfig, params, grads, state):
    """One AdamW step. Returns (new_params, new_state, metrics)."""
    count = state["count"] + 1
    lr = schedule(cfg, state["count"])
    gnorm = global_norm(grads)
    clip = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9)) if cfg.grad_clip else 1.0
    mdt = _mdt(cfg)

    bc1 = 1 - cfg.beta1 ** count.astype(jnp.float32)
    bc2 = 1 - cfg.beta2 ** count.astype(jnp.float32)
    have_master = bool(state["master"])

    def upd(p, g, m, v, master):
        g = g.astype(jnp.float32) * clip
        m32 = m.astype(jnp.float32) * cfg.beta1 + (1 - cfg.beta1) * g
        v32 = v.astype(jnp.float32) * cfg.beta2 + (1 - cfg.beta2) * g * g
        mh = m32 / bc1
        vh = v32 / bc2
        base = master.astype(jnp.float32) if master is not None else p.astype(jnp.float32)
        step = mh / (jnp.sqrt(vh) + cfg.eps) + cfg.weight_decay * base
        new_master = base - lr * step
        return new_master, m32.astype(mdt), v32.astype(mdt)

    p_leaves, treedef = jax.tree.flatten(params)
    g_leaves = treedef.flatten_up_to(grads)
    m_leaves = treedef.flatten_up_to(state["m"])
    v_leaves = treedef.flatten_up_to(state["v"])
    master_leaves = (treedef.flatten_up_to(state["master"]) if have_master
                     else [None] * len(p_leaves))

    new_master, new_m, new_v, new_p = [], [], [], []
    for p, g, m, v, ms in zip(p_leaves, g_leaves, m_leaves, v_leaves, master_leaves):
        nm_master, nm, nv = upd(p, g, m, v, ms)
        new_m.append(nm)
        new_v.append(nv)
        new_p.append(nm_master.astype(p.dtype))
        new_master.append(nm_master)

    new_params = jax.tree.unflatten(treedef, new_p)
    new_state = {
        "m": jax.tree.unflatten(treedef, new_m),
        "v": jax.tree.unflatten(treedef, new_v),
        "count": count,
        "master": (jax.tree.unflatten(treedef, new_master) if have_master else {}),
    }
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_params, new_state, metrics
