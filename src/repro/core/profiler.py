"""Profiling harness (paper §2): measure (time, memory) for train steps.

For every configuration we build a *real* jitted training step (model
forward + CE loss + backward + optimizer update), then record:

  time_s     — median wall-clock of ``steps`` executed steps (compile
               excluded), on JAX's default device;
  mem_bytes  — XLA peak bytes from ``compiled.memory_analysis()`` (the
               AOT analogue of the paper's pynvml polling — see DESIGN.md);
  flops      — loop-aware HLO FLOPs (repro.analysis.hlo), which doubles
               as the paper's Table-2 "FLOPs" feature;
  nsm_edges  — NSM extracted from the step's jaxpr (repro.core.nsm).
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import hlo as hlo_lib
from repro.core import nsm as nsm_lib
from repro.core.features import ProfileRecord
from repro.core.zoo import ZooModel, build_zoo_model
from repro.models.attention import attention_impl

# ---------------------------------------------------------------------------
# Tiny optimizers for the profiling rig (the paper varies the optimizer)
# ---------------------------------------------------------------------------


def make_optimizer(kind: str, lr: float):
    if kind == "sgd":
        def init(p):
            return {}

        def update(g, s, p):
            return jax.tree.map(lambda pp, gg: pp - lr * gg, p, g), s
    elif kind == "momentum":
        def init(p):
            return {"m": jax.tree.map(jnp.zeros_like, p)}

        def update(g, s, p):
            m = jax.tree.map(lambda mm, gg: 0.9 * mm + gg, s["m"], g)
            return jax.tree.map(lambda pp, mm: pp - lr * mm, p, m), {"m": m}
    elif kind in ("adam", "adamw"):
        def init(p):
            return {"m": jax.tree.map(jnp.zeros_like, p),
                    "v": jax.tree.map(jnp.zeros_like, p),
                    "t": jnp.zeros((), jnp.int32)}

        def update(g, s, p):
            t = s["t"] + 1
            m = jax.tree.map(lambda mm, gg: 0.9 * mm + 0.1 * gg, s["m"], g)
            v = jax.tree.map(lambda vv, gg: 0.999 * vv + 0.001 * gg * gg,
                             s["v"], g)
            tf = t.astype(jnp.float32)
            def upd(pp, mm, vv):
                mh = mm / (1 - 0.9 ** tf)
                vh = vv / (1 - 0.999 ** tf)
                step = mh / (jnp.sqrt(vh) + 1e-8)
                if kind == "adamw":
                    step = step + 0.01 * pp
                return pp - lr * step
            return (jax.tree.map(upd, p, m, v), {"m": m, "v": v, "t": t})
    else:
        raise ValueError(kind)
    return init, update


def _softmax_ce(logits, labels):
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


# ---------------------------------------------------------------------------
# Generic step profiling
# ---------------------------------------------------------------------------


def profile_step(step_fn, args, steps: int = 3,
                 donate: Tuple[int, ...] = ()) -> Dict:
    """Compile & run ``step_fn(*args)``; return measurements + features.

    Time and memory are the step's as it runs. The features (NSM edges,
    FLOPs) are read with attention pinned to XLA, so they do not depend on
    which kernel the platform runs: a Pallas call would replace the
    attention equations in the jaxpr and hide its dots from the HLO text.
    """
    traced = jax.jit(step_fn, donate_argnums=donate).trace(*args)
    compiled = traced.lower().compile()
    features = compiled
    if "pallas_call" in str(traced.jaxpr):
        with attention_impl("xla"):
            # a new function: JAX caches a trace by function, not by the pin
            traced = jax.jit(lambda *a: step_fn(*a),
                             donate_argnums=donate).trace(*args)
        features = traced.lower().compile()
    edges = nsm_lib.nsm_edges(traced.jaxpr)
    cost = hlo_lib.analyze_text(features.as_text())
    ma = compiled.memory_analysis()
    mem = (getattr(ma, "argument_size_in_bytes", 0)
           + getattr(ma, "output_size_in_bytes", 0)
           + getattr(ma, "temp_size_in_bytes", 0)
           - getattr(ma, "alias_size_in_bytes", 0))
    # run on fresh zero arguments, made ready before the clock starts; the
    # previous call's arrays are dropped first, so a full-width step holds
    # one set of arguments and outputs at a time
    def zeros():
        return jax.block_until_ready(jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype) if hasattr(s, "shape")
            else s, args))

    jax.block_until_ready(compiled(*zeros()))
    times = []
    for _ in range(steps):
        concrete = zeros()
        t0 = time.perf_counter()
        out = compiled(*concrete)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
        del out, concrete
    return {"time_s": float(np.median(times)), "mem_bytes": float(mem),
            "flops": cost.flops, "nsm_edges": edges}


# ---------------------------------------------------------------------------
# Zoo (CNN) profiling
# ---------------------------------------------------------------------------


def zoo_train_step(model: ZooModel, optimizer: str, lr: float):
    init_opt, update = make_optimizer(optimizer, lr)

    def loss_fn(params, x, y):
        return _softmax_ce(model.apply(params, x), y)

    def step(params, opt_state, x, y):
        g = jax.grad(loss_fn)(params, x, y)
        return update(g, opt_state, params)

    return step, init_opt


def profile_zoo(name: str, batch: int = 16, image: int = 32,
                channels: int = 3, lr: float = 0.1,
                optimizer: str = "sgd", epoch: int = 1,
                steps: int = 3, platform: int = 0) -> ProfileRecord:
    model = build_zoo_model(name, channels, image)
    params = model.init(jax.random.key(0), image)
    step, init_opt = zoo_train_step(model, optimizer, lr)
    opt_state = init_opt(params)
    x = jax.ShapeDtypeStruct((batch, image, image, channels), jnp.float32)
    y = jax.ShapeDtypeStruct((batch,), jnp.int32)
    p_sds = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         params)
    o_sds = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         opt_state)
    meas = profile_step(step, (p_sds, o_sds, x, y), steps=steps)
    n_params = int(sum(int(np.prod(p.shape))
                       for p in jax.tree.leaves(params)))
    return ProfileRecord(
        model_name=name, family="cnn", batch_size=batch, input_size=image,
        channels=channels, learning_rate=lr, epoch=epoch,
        optimizer=optimizer, layers=model.layer_count(), flops=meas["flops"],
        params=n_params, nsm_edges=meas["nsm_edges"],
        time_s=meas["time_s"], mem_bytes=meas["mem_bytes"],
        platform=platform)


# ---------------------------------------------------------------------------
# LM (StackModel) profiling — cross-family generality
# ---------------------------------------------------------------------------


def lm_batch_specs(cfg, batch: int, seq: int) -> Dict:
    """Abstract {tokens, labels[, patches, frames]} train-step inputs.

    Single source of truth for the modality conditionals — the profiler
    and the serving-side trace path must featurize identical graphs.
    """
    b = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
         "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    dt = jnp.float32 if cfg.dtype == "float32" else jnp.bfloat16
    if cfg.cross_every:
        b["patches"] = jax.ShapeDtypeStruct((batch, cfg.vision_seq,
                                             cfg.d_model), dt)
    if cfg.is_encoder_decoder:
        b["frames"] = jax.ShapeDtypeStruct((batch, cfg.audio_seq,
                                            cfg.d_model), dt)
    return b


def lm_trace(cfg, batch: int, seq: int, lr: float = 1e-3):
    """(model, step_fn, state_specs, batch_specs) for one LM train step.

    Shared by the offline profiler and the online PredictionService
    tracer so both featurize the exact same graph.
    """
    from repro.models import build_model
    from repro.train import optimizer as opt_lib
    from repro.train import step as step_lib

    model = build_model(cfg)
    opt_cfg = opt_lib.OptConfig(lr=lr, keep_master=False)
    step = step_lib.make_train_step(model, opt_cfg)
    state_sds = step_lib.state_shapes(model, opt_cfg)
    return model, step, state_sds, lm_batch_specs(cfg, batch, seq)


def lm_record(cfg, model, batch: int, seq: int, *, flops, nsm_edges,
              lr: float = 1e-3, optimizer: str = "adamw",
              time_s: float = 0.0, mem_bytes: float = 0.0,
              platform: int = 0) -> ProfileRecord:
    """The canonical ModelConfig -> ProfileRecord field mapping."""
    return ProfileRecord(
        model_name=cfg.name, family=cfg.family, batch_size=batch,
        input_size=seq, channels=cfg.d_model, learning_rate=lr, epoch=1,
        optimizer=optimizer, layers=cfg.num_layers, flops=flops,
        params=model.param_count(), nsm_edges=nsm_edges,
        time_s=time_s, mem_bytes=mem_bytes, platform=platform)


def profile_lm(cfg, batch: int = 2, seq: int = 64, lr: float = 1e-3,
               optimizer: str = "adamw", steps: int = 3,
               platform: int = 0) -> ProfileRecord:
    model, step, state_sds, b = lm_trace(cfg, batch, seq, lr)
    meas = profile_step(step, (state_sds, b), steps=steps)
    return lm_record(cfg, model, batch, seq, flops=meas["flops"],
                     nsm_edges=meas["nsm_edges"], lr=lr, optimizer=optimizer,
                     time_s=meas["time_s"], mem_bytes=meas["mem_bytes"],
                     platform=platform)
