"""Checkpointing (atomic, elastic) and fault-tolerance runtime."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import checkpoint as ckpt
from repro.ft.runtime import FTConfig, FailureInjector, StepFailure, StepRunner


def _state(v=0.0):
    return {"params": {"w": jnp.full((4, 4), v), "b": jnp.zeros((4,))},
            "step": jnp.asarray(7, jnp.int32)}


def test_save_restore_roundtrip(tmp_path):
    d = str(tmp_path / "ck")
    ckpt.save(d, 7, _state(1.5))
    assert ckpt.latest_step(d) == 7
    out = ckpt.restore(d, 7, _state())
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                  np.full((4, 4), 1.5))
    assert int(out["step"]) == 7


def test_atomicity_no_partial_dirs(tmp_path):
    d = str(tmp_path / "ck")
    ckpt.save(d, 1, _state())
    ckpt.save(d, 2, _state())
    names = set(os.listdir(d))
    assert not any(n.startswith(".tmp") for n in names)
    assert ckpt.all_steps(d) == [1, 2]


def test_gc_keeps_last_k(tmp_path):
    d = str(tmp_path / "ck")
    for s in range(1, 6):
        ckpt.save(d, s, _state(), keep=2)
    assert ckpt.all_steps(d) == [4, 5]


def test_restore_missing_key_raises(tmp_path):
    d = str(tmp_path / "ck")
    ckpt.save(d, 1, {"a": jnp.zeros(2)})
    with pytest.raises(ValueError):
        ckpt.restore(d, 1, {"a": jnp.zeros(2), "b": jnp.zeros(3)})


def test_restore_casts_dtype(tmp_path):
    d = str(tmp_path / "ck")
    ckpt.save(d, 1, {"w": jnp.ones((2, 2), jnp.float32)})
    out = ckpt.restore(
        d, 1, {"w": jax.ShapeDtypeStruct((2, 2), jnp.bfloat16)})
    assert out["w"].dtype == jnp.bfloat16


def test_elastic_restore_resharding(tmp_path):
    """Restore onto a (1-device) mesh sharding — the elastic-resume path."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_host_mesh
    d = str(tmp_path / "ck")
    state = {"w": jnp.arange(16.0).reshape(4, 4)}
    ckpt.save(d, 3, state)
    mesh = make_host_mesh(1, 1)
    sh = {"w": NamedSharding(mesh, P("data", None))}
    out = ckpt.restore(d, 3, state, sh)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(state["w"]))
    assert out["w"].sharding == sh["w"]


# -- fault tolerance ---------------------------------------------------------


def test_runner_retries_transient_failures():
    inj = FailureInjector(fail_on_calls=(1, 2))
    fn = inj.wrap(lambda x: x + 1)
    runner = StepRunner(fn, FTConfig(max_retries=2))
    assert runner(41) == 42
    assert runner.retries == 2


def test_runner_gives_up_after_max_retries():
    inj = FailureInjector(fail_on_calls=(1, 2, 3, 4))
    runner = StepRunner(inj.wrap(lambda x: x), FTConfig(max_retries=1))
    with pytest.raises(StepFailure):
        runner(1)


def test_runner_keeps_first_error_as_cause():
    inj = FailureInjector(fail_on_calls=(1, 2, 3))
    runner = StepRunner(inj.wrap(lambda x: x), FTConfig(max_retries=2))
    with pytest.raises(StepFailure) as info:
        runner(1)
    assert "call 1" in str(info.value.__cause__)
    assert inj.calls == 3 and runner.retries == 2


@pytest.mark.parametrize("error", [
    TypeError("unexpected argument"),
    ValueError("sharding mismatch"),
    RuntimeError("INVALID_ARGUMENT: Attempt to donate the same buffer twice"),
])
def test_runner_does_not_retry_argument_errors(error):
    calls = []

    def step(x):
        calls.append(x)
        raise error

    runner = StepRunner(step, FTConfig(max_retries=2))
    with pytest.raises(StepFailure, match="cannot be retried") as info:
        runner(1)
    assert info.value.__cause__ is error
    assert len(calls) == 1 and runner.retries == 0


def test_runner_does_not_retry_after_donation():
    """A call that failed after consuming its donated state cannot rerun."""
    donate = jax.jit(lambda s: s + 1, donate_argnums=0)
    calls = []

    def step(state):
        calls.append(1)
        donate(state)  # the step's buffers are gone ...
        raise RuntimeError("device lost")  # ... when the device fails

    runner = StepRunner(step, FTConfig(max_retries=2))
    with pytest.raises(StepFailure, match="cannot be retried") as info:
        runner(jnp.ones(4))
    assert "device lost" in str(info.value.__cause__)
    assert len(calls) == 1 and runner.retries == 0


def test_straggler_detection_with_prediction():
    import time
    flags = []
    def slow_then_fast(x):
        time.sleep(0.05 if len(flags) == 0 and not slow_then_fast.done else 0)
        slow_then_fast.done = True
        return x
    slow_then_fast.done = False
    runner = StepRunner(slow_then_fast, FTConfig(straggler_factor=2.0),
                        predicted_step_s=0.005,
                        on_straggler=lambda i, dt: flags.append(dt))
    runner(1)   # slow step -> flagged
    runner(1)
    assert runner.stragglers >= 1 and len(flags) >= 1


def test_master_weights_share_no_buffer_with_params():
    """The step donates params and master together; XLA refuses one buffer
    donated twice, so an fp32 param's master must be its own copy."""
    from repro.train import optimizer as opt_lib
    params = {"w": jnp.ones((4, 4), jnp.bfloat16),
              "scale": jnp.ones((4,), jnp.float32)}
    state = opt_lib.init_opt_state(opt_lib.OptConfig(), params)
    for name, p in params.items():
        master = state["master"][name]
        assert master.dtype == jnp.float32
        assert master.unsafe_buffer_pointer() != p.unsafe_buffer_pointer()


def test_trainer_resumes_after_simulated_crash(tmp_path):
    """Kill training mid-run; a fresh Trainer resumes from the checkpoint."""
    from repro.configs import get_config, reduced_config
    from repro.models import build_model
    from repro.train import optimizer as opt_lib
    from repro.train.loop import LoopConfig, Trainer

    cfg = reduced_config(get_config("qwen2-0.5b"))
    model = build_model(cfg)
    d = str(tmp_path / "ck")
    lc = LoopConfig(steps=6, batch=2, seq=32, ckpt_every=2, ckpt_dir=d,
                    log_every=1)
    t1 = Trainer(model, opt_lib.OptConfig(), lc)
    t1.run(steps=4)  # "crash" after 4 steps (ckpts at 2,4)
    assert ckpt.latest_step(d) == 4

    t2 = Trainer(model, opt_lib.OptConfig(), lc)
    log = t2.run()  # resumes at 4, finishes 6
    steps_seen = [r["step"] for r in t2.metrics_log]
    assert min(steps_seen) >= 4
    assert ckpt.latest_step(d) == 6
