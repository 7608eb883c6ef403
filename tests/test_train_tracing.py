"""The train path's spans, counters and named scopes, at a reduced width.

Host spans (``loader/*``, ``step/*``, ``ckpt/save``) go into the
profiler's own trace, next to the device ops; the step runner counts
steps, retries, stragglers and compiles; each model layer's ops carry a
``jax.named_scope`` in their ``op_name``. None of it may change what the
oracle reads: the train step's jaxpr and its NSM edges are pinned to the
digests they had before the scopes were added.
"""

import dataclasses
import glob
import hashlib
import json
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced_config
from repro.data.pipeline import ShardedLoader, SyntheticLM
from repro.ft.runtime import FailureInjector, FTConfig, StepRunner
from repro.models import build_model
from repro.obs import events
from repro.train import optimizer as opt_lib
from repro.train import step as step_lib
from repro.train.loop import LoopConfig, Trainer

SCOPES = ("embed", "attention", "mlp", "norm", "lm_head", "optimizer")


@pytest.fixture
def no_compile_cache():
    """Every compile reaches the backend, so the compile counter sees it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _trainer(steps=3, **kw):
    model = build_model(reduced_config(get_config("qwen2-0.5b")))
    return Trainer(model, opt_lib.OptConfig(),
                   LoopConfig(steps=steps, batch=2, seq=32, log_every=1, **kw))


def _host_spans(trace_dir):
    """(thread index, name, start_ns, end_ns) of every host event."""
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out += [(i, e.name, e.start_ns, e.end_ns) for e in line.events]
    return out


def _count(runner, name):
    return runner.metrics.snapshot()[name]["value"]


def _within(inner, outer):
    return outer[2] <= inner[2] and inner[3] <= outer[3]


def test_three_trainer_steps_are_traced_once_per_step(tmp_path):
    trainer = _trainer(ckpt_dir=str(tmp_path / "ck"), ckpt_every=0)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        trainer.run()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path / "trace"))
    steps = sorted((s for s in spans if s[1] == "train"), key=lambda s: s[2])
    assert len(steps) == 3
    thread = steps[0][0]
    for name in ("loader/wait", "step/dispatch", "step/block"):
        mine = [s for s in spans if s[1] == name]
        assert len(mine) == 3, name
        assert all(s[0] == thread for s in mine), name
        # one in each step, in the step's own span
        assert [sum(_within(s, st) for s in mine) for st in steps] == [1, 1, 1]
    for st in steps:
        inside = sorted((s for s in spans if s[0] == thread and _within(s, st)
                         and "/" in s[1]), key=lambda s: s[2])
        assert [s[1] for s in inside] == ["loader/wait", "step/dispatch",
                                          "step/block"]
    produce = [s for s in spans if s[1] == "loader/produce"]
    assert produce and all(s[0] != thread for s in produce)
    assert len([s for s in spans if s[1] == "ckpt/save"]) == 1
    assert _count(trainer.runner, "steps") == 3 and trainer.runner.retries == 0
    # the logged step time is the runner's own
    assert [r["step_time_s"] for r in trainer.metrics_log] == pytest.approx(
        list(trainer.runner.history))


def test_a_retry_is_counted_and_traced(tmp_path):
    inj = FailureInjector(fail_on_calls=(1,))
    runner = StepRunner(inj.wrap(jax.jit(lambda x: x + 1)), FTConfig(max_retries=2))
    x = jnp.ones(4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = runner(x)
    finally:
        jax.profiler.stop_trace()
    assert float(out[0]) == 2.0
    assert runner.retries == 1 and _count(runner, "retries") == 1
    assert _count(runner, "steps") == 1
    spans = _host_spans(str(tmp_path))
    retry, = [s for s in spans if s[1] == "step/retry"]
    dispatch = sorted((s for s in spans if s[1] == "step/dispatch"),
                      key=lambda s: s[2])
    assert len(dispatch) == 2  # the failed attempt and the retry
    assert not _within(dispatch[0], retry) and _within(dispatch[1], retry)
    block, = [s for s in spans if s[1] == "step/block"]
    assert _within(block, retry)


def test_a_new_batch_shape_is_one_compile(no_compile_cache):
    trainer = _trainer()
    state = trainer.init_state()
    runner = trainer.runner
    t0 = time.time()

    def batch(seq):
        return jax.tree.map(jnp.asarray,
                            SyntheticLM(512, 2, seq).batch_at(0))

    moved = []
    for seq in (32, 32, 48, 48):
        b = batch(seq)
        before = runner.compiles
        state, _ = runner(state, b)
        moved.append(runner.compiles - before)
    assert moved == [1, 0, 1, 0]
    recompiles = [e for e in events.tail()
                  if e["event"] == "recompile" and e["ts"] >= t0]
    assert [(e["step"], e["compiles"]) for e in recompiles] == [(0, 1), (2, 1)]
    assert all(e["seconds"] > 0 for e in recompiles)
    assert _count(runner, "compiles") == 2


def test_history_is_bounded_and_stragglers_take_the_step_count():
    delay = [0.0]
    flagged = []

    def step(x):
        time.sleep(delay[0])
        return x

    runner = StepRunner(step, FTConfig(straggler_factor=3.0),
                        predicted_step_s=0.02,
                        on_straggler=lambda i, dt: flagged.append(i))
    for _ in range(StepRunner.HISTORY + 10):
        runner(1)
    assert len(runner.history) == StepRunner.HISTORY
    assert _count(runner, "steps") == StepRunner.HISTORY + 10
    t0 = time.time()
    delay[0] = 0.1
    runner(1)
    assert flagged == [StepRunner.HISTORY + 10] and runner.stragglers == 1
    ev, = [e for e in events.tail()
           if e["event"] == "straggler" and e["ts"] >= t0]
    assert ev["step"] == StepRunner.HISTORY + 10 and ev["seconds"] >= 0.1


class _CountingSource:
    def __init__(self):
        self.calls = []
        self.lock = threading.Lock()

    def batch_at(self, step):
        with self.lock:
            self.calls.append(step)
        return {"tokens": jnp.full((1, 4), step, jnp.int32)}


def test_a_full_queue_keeps_the_batch_it_made():
    src = _CountingSource()
    loader = ShardedLoader(src, prefetch=1)
    try:
        time.sleep(1.3)  # the put of step 1 times out twice
        assert src.calls == [0, 1]
        got = [int(next(loader)["tokens"][0, 0]) for _ in range(3)]
        assert got == [0, 1, 2]
    finally:
        loader.close()


def _scope_names(lowered):
    """Every name on an op_name path of the lowered program's HLO."""
    text = lowered.as_text(dialect="hlo", debug_info=True)
    return {tok for name in re.findall(r'op_name="([^"]*)"', text)
            for tok in re.findall(r"[^/()]+", name)}


def _lowered_train_step(arch):
    cfg = reduced_config(get_config(arch))
    model = build_model(cfg)
    opt = opt_lib.OptConfig()
    step = step_lib.make_train_step(model, opt)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 32), jnp.int32)}
    if cfg.cross_every:
        batch["patches"] = jax.ShapeDtypeStruct((2, cfg.vision_seq, cfg.d_model),
                                                jnp.float32)
    return jax.jit(step).lower(step_lib.state_shapes(model, opt), batch)


@pytest.mark.parametrize("arch,scopes", [
    ("qwen2-0.5b", SCOPES),
    ("jamba-v0.1-52b", SCOPES + ("moe", "ssm")),
    ("llama-3.2-vision-90b", SCOPES),
])
def test_every_scope_names_ops_of_the_train_step(arch, scopes):
    assert set(scopes) <= _scope_names(_lowered_train_step(arch))


def test_decode_ops_are_attention_scoped():
    model = build_model(reduced_config(get_config("qwen2-0.5b")))
    params = model.init_shape()
    cache = jax.eval_shape(lambda: model.init_cache(2, 16))
    tokens = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((2,), jnp.int32)
    names = _scope_names(jax.jit(model.decode_step).lower(params, cache, tokens, pos))
    assert {"embed", "attention", "mlp", "norm", "lm_head"} <= names


# Digests of the jaxpr's printed form (which carries no name stack) and of
# the NSM edges, taken at the commit before the scopes were added.
JAXPR_DIGESTS = {
    ("float32", "none"): "10fbf8a05dc6663aad7c77d20b84dc584779bde4c252f564c5580f1870fec595",
    ("bfloat16", "none"): "6862f14116a20b4eeb688e6c5e0aeda0d04b8b71c8d6a26f11741082e57817fe",
    ("bfloat16", "full"): "a12a42fed200d5682c3ae4e55ca2515a1fa9705e846fd84f1c9bdbe1d0f111cb",
}
NSM_DIGEST = "bbc45e031f7cf47157d3522410060fc5e70077f89999345ed674c295758c45f1"


@pytest.mark.parametrize("dtype,remat", sorted(JAXPR_DIGESTS))
def test_scopes_leave_the_train_steps_jaxpr_alone(dtype, remat):
    cfg = dataclasses.replace(reduced_config(get_config("qwen2-0.5b"), dtype=dtype),
                              remat=remat)
    model = build_model(cfg)
    opt = opt_lib.OptConfig()
    step = step_lib.make_train_step(model, opt)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 32), jnp.int32)}
    text = str(jax.make_jaxpr(step)(step_lib.state_shapes(model, opt), batch))
    assert hashlib.sha256(text.encode()).hexdigest() == JAXPR_DIGESTS[dtype, remat]


def test_scopes_leave_the_nsm_features_alone():
    from repro.serve.prediction_service import trace_query
    rec = trace_query(reduced_config(get_config("qwen2-0.5b")), 2, 32)
    edges = json.dumps(sorted((f"{a}->{b}", v) for (a, b), v in rec.nsm_edges.items()))
    assert hashlib.sha256(edges.encode()).hexdigest() == NSM_DIGEST
