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
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional


class StepFailure(RuntimeError):
    pass


@dataclasses.dataclass
class FTConfig:
    max_retries: int = 2
    straggler_factor: float = 3.0
    watchdog_timeout_s: Optional[float] = None
    min_history: int = 5


class StepRunner:
    """Wraps a step callable with retries + timing + straggler flags."""

    def __init__(self, step_fn: Callable, cfg: FTConfig = FTConfig(),
                 predicted_step_s: Optional[float] = None,
                 on_straggler: Optional[Callable[[int, float], None]] = None):
        self.step_fn = step_fn
        self.cfg = cfg
        self.predicted = predicted_step_s
        self.on_straggler = on_straggler
        self.history: List[float] = []
        self.retries = 0
        self.stragglers = 0

    def _reference_time(self) -> Optional[float]:
        if self.predicted is not None:
            return self.predicted
        if len(self.history) >= self.cfg.min_history:
            s = sorted(self.history[-50:])
            return s[len(s) // 2]
        return None

    def __call__(self, *args):
        first_err = None
        attempts = self.cfg.max_retries + 1
        for attempt in range(attempts):
            t0 = time.perf_counter()
            try:
                out = jax_block(self.step_fn(*args))
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
                    self.retries += 1
                continue
            dt = time.perf_counter() - t0
            ref = self._reference_time()
            if ref is not None and dt > self.cfg.straggler_factor * ref:
                self.stragglers += 1
                if self.on_straggler:
                    self.on_straggler(len(self.history), dt)
            if (self.cfg.watchdog_timeout_s
                    and dt > self.cfg.watchdog_timeout_s):
                raise StepFailure(
                    f"watchdog: step took {dt:.1f}s "
                    f"> {self.cfg.watchdog_timeout_s}s")
            self.history.append(dt)
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
    import jax
    if isinstance(err, (TypeError, ValueError)):
        return True
    if str(err).startswith("INVALID_ARGUMENT"):
        return True
    return any(getattr(x, "is_deleted", lambda: False)()
               for x in jax.tree.leaves(args))


def jax_block(out):
    import jax
    return jax.block_until_ready(out)


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
