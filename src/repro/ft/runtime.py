"""Fault tolerance: retrying step runner, straggler detection, watchdog.

Straggler detection uses DNNAbacus as its reference: if the cost
predictor has been fit for this platform, a step whose wall time exceeds
``straggler_factor x`` the *predicted* step time is flagged (the paper's
scheduling use-case, applied online). Without a predictor the detector
falls back to a running median.

On a multi-host deployment, ``on_straggler``/``on_failure`` hooks feed
the cluster controller (re-slice the data axis and restart from the last
atomic checkpoint — see repro.ckpt). Everything here is host-local and
unit-testable.

Each call is traced on the profiler's clock: ``step/dispatch`` (the step
callable until it returns), ``step/block`` (``block_until_ready``) and,
around any attempt after the first, ``step/retry``. The runner's
``metrics`` registry counts ``steps``, ``retries``, ``stragglers`` and
``compiles`` (XLA compilations in the process while a step ran); a step
that compiled emits a ``recompile`` event and a straggler a
``straggler`` event on ``repro.obs.events``.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Callable, Optional

import jax

from repro.obs import events
from repro.obs.metrics import MetricsRegistry


class StepFailure(RuntimeError):
    pass


@dataclasses.dataclass
class FTConfig:
    max_retries: int = 2
    straggler_factor: float = 3.0
    watchdog_timeout_s: Optional[float] = None
    min_history: int = 5


class _CompileCount:
    """XLA compilations in this process, from one ``jax.monitoring``
    listener registered on first use."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0
        self._registered = False

    def _listen(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            with self._lock:
                self._n += 1

    def total(self) -> int:
        with self._lock:
            if not self._registered:
                jax.monitoring.register_event_duration_secs_listener(self._listen)
                self._registered = True
            return self._n


COMPILES = _CompileCount()


class StepRunner:
    """Wraps a step callable with retries + timing + straggler flags."""

    HISTORY = 50  # step times the straggler median is taken over

    def __init__(self, step_fn: Callable, cfg: FTConfig = FTConfig(),
                 predicted_step_s: Optional[float] = None,
                 on_straggler: Optional[Callable[[int, float], None]] = None):
        self.step_fn = step_fn
        self.cfg = cfg
        self.predicted = predicted_step_s
        self.on_straggler = on_straggler
        self.history: collections.deque = collections.deque(maxlen=self.HISTORY)
        self.metrics = MetricsRegistry(namespace="train")
        self._steps = self.metrics.counter("steps", "steps completed")
        self._retries = self.metrics.counter("retries", "attempts after a failure")
        self._stragglers = self.metrics.counter(
            "stragglers", "steps over straggler_factor x the reference time")
        self._compiles = self.metrics.counter(
            "compiles", "XLA compilations while a step ran")

    @property
    def retries(self) -> int:
        return self._retries.value

    @property
    def stragglers(self) -> int:
        return self._stragglers.value

    @property
    def compiles(self) -> int:
        return self._compiles.value

    def _reference_time(self) -> Optional[float]:
        if self.predicted is not None:
            return self.predicted
        if len(self.history) >= self.cfg.min_history:
            s = sorted(self.history)
            return s[len(s) // 2]
        return None

    def __call__(self, *args):
        first_err = None
        attempts = self.cfg.max_retries + 1
        compiled_before = COMPILES.total()
        for attempt in range(attempts):
            retry = (jax.profiler.TraceAnnotation("step/retry") if attempt
                     else contextlib.nullcontext())
            with retry:
                t0 = time.perf_counter()
                try:
                    with jax.profiler.TraceAnnotation("step/dispatch"):
                        out = self.step_fn(*args)
                    with jax.profiler.TraceAnnotation("step/block"):
                        out = jax.block_until_ready(out)
                except StepFailure:
                    raise
                except Exception as e:
                    if first_err is None:
                        first_err = e
                    if _hopeless(e, args):
                        raise StepFailure(
                            f"step failed on attempt {attempt + 1} and cannot "
                            f"be retried: {type(e).__name__}: {e}") from first_err
                    if attempt + 1 < attempts:
                        self._retries.inc()
                    continue
            dt = time.perf_counter() - t0
            step = self._steps.value
            compiled = COMPILES.total() - compiled_before
            if compiled:
                self._compiles.inc(compiled)
                events.emit("recompile", step=step, seconds=dt,
                            compiles=compiled)
            ref = self._reference_time()
            if ref is not None and dt > self.cfg.straggler_factor * ref:
                self._stragglers.inc()
                events.emit("straggler", step=step, seconds=dt,
                            reference_s=ref)
                if self.on_straggler:
                    self.on_straggler(step, dt)
            if (self.cfg.watchdog_timeout_s
                    and dt > self.cfg.watchdog_timeout_s):
                raise StepFailure(
                    f"watchdog: step took {dt:.1f}s "
                    f"> {self.cfg.watchdog_timeout_s}s")
            self.history.append(dt)
            self._steps.inc()
            return out
        raise StepFailure(
            f"step failed after {attempts} attempts") from first_err


def _hopeless(err: Exception, args) -> bool:
    """True for a failure that every retry would repeat.

    Argument errors: ``TypeError``/``ValueError`` (jit's sharding
    mismatches among them) and runtime errors with XLA's
    ``INVALID_ARGUMENT`` status. And any failure after the call consumed
    its donated buffers, which a retry would need again.
    """
    if isinstance(err, (TypeError, ValueError)):
        return True
    if str(err).startswith("INVALID_ARGUMENT"):
        return True
    return any(getattr(x, "is_deleted", lambda: False)()
               for x in jax.tree.leaves(args))


@dataclasses.dataclass
class FailureInjector:
    """Deterministic fault injection for tests: raises on listed calls."""

    fail_on_calls: tuple = ()
    exception: type = RuntimeError
    calls: int = 0

    def wrap(self, fn):
        def inner(*args):
            self.calls += 1
            if self.calls in self.fail_on_calls:
                raise self.exception(f"injected failure at call {self.calls}")
            return fn(*args)
        return inner
