"""Training loop: jit + shardings + checkpoint/restart + fault tolerance.

The loop is deliberately host-driven and restartable: all state lives in
the (atomic) checkpoint, the data stream is deterministic in step, and
the mesh shape may change between runs (elastic restart) because restores
re-shard. ``run()`` returns the metrics history for tests/benchmarks.
Each step is a ``StepTraceAnnotation("train")`` for the profiler's step
view, and checkpoint writes are ``ckpt/save`` spans.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.ckpt import checkpoint as ckpt_lib
from repro.data.pipeline import ShardedLoader, SyntheticLM
from repro.distributed import sharding as shd
from repro.ft.runtime import FTConfig, StepRunner
from repro.train import optimizer as opt_lib
from repro.train import step as step_lib


@dataclasses.dataclass
class LoopConfig:
    steps: int = 20
    batch: int = 8
    seq: int = 128
    ckpt_every: int = 10
    log_every: int = 5
    ckpt_dir: Optional[str] = None
    seed: int = 0
    zero: bool = True
    accum: int = 1
    predicted_step_s: Optional[float] = None  # DNNAbacus admission/straggler


class Trainer:
    def __init__(self, model, opt_cfg: opt_lib.OptConfig, loop_cfg: LoopConfig,
                 mesh=None, rules: Optional[shd.ShardingRules] = None,
                 source=None):
        self.model = model
        self.opt_cfg = opt_cfg
        self.cfg = loop_cfg
        self.mesh = mesh
        self.rules = rules or shd.ShardingRules()
        self.metrics_log: List[Dict[str, Any]] = []
        self.source = source or SyntheticLM(
            model.cfg.vocab_size, loop_cfg.batch, loop_cfg.seq, loop_cfg.seed)

        step_fn = step_lib.make_train_step(model, opt_cfg, accum=loop_cfg.accum)
        if mesh is not None:
            self.state_sh = step_lib.state_shardings(
                model, opt_cfg, mesh, self.rules, zero=loop_cfg.zero)
            sample = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                self.source.batch_at(0))
            self.batch_sh = step_lib.batch_shardings(mesh, sample, self.rules)
            # the returned state must keep the layout the step takes, or
            # the next call sees arguments that no longer match
            self.jstep = jax.jit(step_fn,
                                 in_shardings=(self.state_sh, self.batch_sh),
                                 out_shardings=(self.state_sh, None),
                                 donate_argnums=(0,))
        else:
            self.state_sh = None
            self.batch_sh = None
            self.jstep = jax.jit(step_fn, donate_argnums=(0,))
        self.runner = StepRunner(self.jstep, FTConfig(),
                                 predicted_step_s=loop_cfg.predicted_step_s)

    # -- state management -------------------------------------------------
    def init_state(self):
        state = step_lib.init_state(self.model, self.opt_cfg,
                                    jax.random.key(self.cfg.seed))
        if self.state_sh is not None:
            state = jax.tree.map(jax.device_put, state, self.state_sh)
        return state

    def restore_or_init(self):
        d = self.cfg.ckpt_dir
        if d:
            step = ckpt_lib.latest_step(d)
            if step is not None:
                like = step_lib.state_shapes(self.model, self.opt_cfg)
                state = ckpt_lib.restore(d, step, like, self.state_sh)
                return state, step
        return self.init_state(), 0

    # -- main loop ---------------------------------------------------------
    def run(self, steps: Optional[int] = None) -> List[Dict[str, Any]]:
        steps = steps if steps is not None else self.cfg.steps
        state, start = self.restore_or_init()
        loader = ShardedLoader(self.source, self.batch_sh, start_step=start)
        try:
            for i in range(start, steps):
                with jax.profiler.StepTraceAnnotation("train", step_num=i):
                    batch = next(loader)
                    state, metrics = self.runner(state, batch)
                    if i % self.cfg.log_every == 0 or i == steps - 1:
                        rec = {k: float(v) for k, v in metrics.items()}
                        rec.update(step=i, step_time_s=self.runner.history[-1])
                        self.metrics_log.append(rec)
                    if (self.cfg.ckpt_dir and self.cfg.ckpt_every
                            and (i + 1) % self.cfg.ckpt_every == 0):
                        self._save(i + 1, state)
            if self.cfg.ckpt_dir:
                self._save(steps, state)
        finally:
            loader.close()
        return self.metrics_log

    def _save(self, step: int, state) -> None:
        with jax.profiler.TraceAnnotation("ckpt/save"):
            ckpt_lib.save(self.cfg.ckpt_dir, step, state)

    def write_log(self, path: str) -> None:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            for rec in self.metrics_log:
                f.write(json.dumps(rec) + "\n")
