"""Training traffic: the launcher's train step, back to back.

Set-up builds the one object the window drives: the program's
``Trainer`` (its donated, jitted step ``jstep``) with the optimizer the
traffic file states, fed by the program's ``ShardedLoader`` from Zipf
token rows made from the seed. The weights are made on the
device in one jitted call. The first ``check_steps`` steps compile the
step and are read for the check (loss, first clipped gradient, change of
the weights). Those steps and the window call the same compiled step,
``Trainer.jstep``, on the same feed. The window keeps ``AHEAD_S``
seconds of steps dispatched ahead of the one it waits for, so that the
chip stays fed while the host stands still, and reads no loss until the
step is that far behind; when ``--seconds`` have passed it sends nothing
more, waits for every step it sent, and reads the clock after that wait:
the rate is all of those steps over all of that time. The program's
``StepRunner``, which blocks on every step, is not in the window.
After the window the device's peak memory is read (and, in a traced run,
the compiled step's footprint and its HLO text, whose ``op_name``
metadata names each op's scopes), the program's state is freed, and the
plain reference trains the same weights on the same rows for the same
steps. ``correct`` holds the program's readings to the
reference's by the limits in the workload file (``checks.py``).

Traffic keys: ``batch``, ``seq``, ``tokens`` and ``zipf_a`` (the token
law, ``weights.Tokens``), ``check_steps``,
``ref_rows_per_block`` (rows the reference holds at once), ``optimizer``
(the program's ``OptConfig``).
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import math
import time
from types import SimpleNamespace
from typing import Any, Dict

from chipbench import checks, flops
from chipbench import weights as W
from chipbench.reference import train as reference

AHEAD_S = 6.0  # seconds of steps the window keeps dispatched ahead


def program_config(cfg: Dict[str, Any]):
    """The program's ModelConfig, with every size the configuration file sets."""
    from repro.configs import get_config

    prog = cfg["program"]
    fields = {}
    for field, key in prog["fields"].items():
        val = cfg
        for part in key.split("."):
            val = val[part]
        fields[field] = val
    mcfg = dataclasses.replace(get_config(prog["arch"]), **fields)
    for field, want in prog.get("fixed", {}).items():
        if getattr(mcfg, field) != want:
            raise ValueError(f"{prog['arch']}: {field} is {getattr(mcfg, field)!r}, "
                             f"the configuration states {want!r}")
    return mcfg


class CompileCounter:
    """Counts XLA compilations while it is active."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.active = False

    def __call__(self, event: str, duration: float, **kw) -> None:
        if self.active and event == self.EVENT:
            self.count += 1

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self)
        self.active = True
        return self

    def __exit__(self, *exc):
        self.active = False


def step_footprint(compiled) -> int:
    """Device bytes a compiled step holds at once: its arguments, its
    outputs that do not reuse a donated argument, and its temporaries."""
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes)


def setup(cfg: Dict[str, Any], tr: Dict[str, Any], seed: int) -> SimpleNamespace:
    """The Trainer, its state after the checked steps, its feed, readings,
    and the shortest checked step after the first, in seconds."""
    import jax
    import jax.numpy as jnp

    from repro.data.pipeline import ShardedLoader
    from repro.models import build_model
    from repro.train import optimizer as opt_lib
    from repro.train.loop import LoopConfig, Trainer

    batch, seq = tr["batch"], tr["seq"]
    model = build_model(program_config(cfg))
    opt_cfg = opt_lib.OptConfig(**tr["optimizer"])
    data = W.tokens(cfg, tr, seed)
    trainer = Trainer(model, opt_cfg, LoopConfig(batch=batch, seq=seq, seed=seed),
                      source=data)

    specs = W.leaf_specs(cfg)
    key = W.seed_key(seed)
    want = jax.tree.map(lambda s: (s.shape, s.dtype), model.init_shape())
    have = jax.tree.map(lambda s: (s.shape, s.dtype),
                        jax.eval_shape(lambda k: W.make_params(specs, k), key))
    if want != have:
        raise ValueError("the benchmark's weights do not match the program's layout")

    def init(k):
        params = W.make_params(specs, k)
        return {"params": params, "opt": opt_lib.init_opt_state(opt_cfg, params),
                "step": jnp.zeros((), jnp.int32)}

    state = jax.jit(init)(key)
    norms = jax.jit(reference.leaf_norms)
    change = jax.jit(lambda k, t: reference.change_norms(specs, k, t))
    loader = ShardedLoader(data, None)

    readings: Dict[str, Any] = {"loss": []}
    step_s = math.inf
    for t in range(tr["check_steps"]):
        t0 = time.perf_counter()
        state, out = jax.block_until_ready(trainer.jstep(state, next(loader)))
        if t:
            step_s = min(step_s, time.perf_counter() - t0)
        readings["loss"].append(float(out["loss"]))
        if t == 0:
            scale = 1.0 / (1.0 - opt_cfg.beta1)  # m after one step = (1 - b1) g
            readings["grad"] = {p: float(x) * scale
                                for p, x in norms(state["opt"]["m"]).items()}
    kept = state["opt"]["master"] or state["params"]
    readings["change"] = {p: float(x) for p, x in change(key, kept).items()}
    return SimpleNamespace(trainer=trainer, state=state, loader=loader,
                           readings=readings, step_s=step_s)


def run(ctx) -> Dict[str, Any]:
    import jax

    cfg, tr = ctx.config, ctx.traffic
    batch, seq = tr["batch"], tr["seq"]
    s = setup(cfg, tr, ctx.seed)
    jstep, state, loader, readings = s.trainer.jstep, s.state, s.loader, s.readings
    ahead = max(1, math.ceil(AHEAD_S / s.step_s)) if math.isfinite(s.step_s) else 1
    del s

    if ctx.trace:
        jax.profiler.start_trace(ctx.trace_dir)
    steps = 0
    sent = collections.deque()  # the losses of steps not yet waited for
    with CompileCounter() as compiles:
        t0 = time.perf_counter()
        setup_s = t0 - ctx.t0
        with jax.profiler.TraceAnnotation("window"):
            while True:
                with jax.profiler.StepTraceAnnotation("train", step_num=steps):
                    with jax.profiler.TraceAnnotation("data"):
                        b = next(loader)
                    with jax.profiler.TraceAnnotation("dispatch"):
                        state, out = jstep(state, b)
                    sent.append(out["loss"])
                    if len(sent) > ahead:
                        with jax.profiler.TraceAnnotation("step"):
                            sent.popleft().block_until_ready()
                steps += 1
                if time.perf_counter() - t0 >= ctx.seconds:
                    break
            with jax.profiler.TraceAnnotation("step"):
                jax.block_until_ready((state, out, list(sent)))
        t1 = time.perf_counter()
    if ctx.trace:
        jax.profiler.stop_trace()
    last_loss = float(out["loss"])

    stats = jax.devices()[0].memory_stats() or {}
    loader.close()
    step_bytes = hlo_text = None
    if ctx.trace:  # one compile (from the cache) gives footprint and op names
        compiled = jstep.lower(state, b).compile()
        step_bytes, hlo_text = step_footprint(compiled), compiled.as_text()
    state_step = int(state["step"])
    del state, out, b, jstep, loader, sent
    gc.collect()

    t_ref = time.perf_counter()
    ref = reference.run(cfg, tr, ctx.seed, "highest", steps=tr["check_steps"])
    ref_s = time.perf_counter() - t_ref
    nums = checks.numbers(readings, ref)
    limits = ctx.workload["limits"]
    tokens_per_s = steps * batch * seq / (t1 - t0)
    return {
        "correct": checks.judge(nums, limits) and math.isfinite(last_loss),
        "attempted": steps,
        "failed": 0,
        "e2e": {"train_tokens_per_s": tokens_per_s, "setup_s": setup_s},
        "checks": {k: [nums[k], limits[k]] for k in limits},
        "memory_peak_bytes": stats.get("peak_bytes_in_use", 0),
        "bytes_limit": stats.get("bytes_limit", 0),
        "step_bytes": step_bytes,
        "hlo_text": hlo_text,
        "tokens_per_s": tokens_per_s,
        "flops_per_token": flops.train_flops_per_token(cfg, seq),
        "log": {"window_steps": steps, "window_s": t1 - t0, "ahead": ahead,
                "state_step": state_step,
                "compiles_in_window": compiles.count, "reference_s": ref_s,
                "last_loss": last_loss, "program": readings["loss"],
                "reference": ref["loss"], "memory_stats": stats,
                "readings": {"program": readings, "reference": ref}},
    }
