"""Sharded data pipeline: synthetic + memmap token sources, prefetching.

The loader produces global batches already placed on the mesh with the
``batch``-axis sharding. Sources are deterministic in (seed, step) so an
elastic restart resumes the exact token stream from the checkpointed step
— a data pipeline requirement for reproducible fault recovery.
"""

from __future__ import annotations

import queue
import threading
from typing import Dict, Iterator, Optional

import jax
import numpy as np


class SyntheticLM:
    """Deterministic synthetic next-token data (zipf-ish unigram stream)."""

    def __init__(self, vocab_size: int, batch: int, seq: int, seed: int = 0):
        self.vocab_size = vocab_size
        self.batch = batch
        self.seq = seq
        self.seed = seed

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, step]))
        # zipf-like marginal so losses are non-degenerate
        z = rng.zipf(1.3, size=(self.batch, self.seq + 1))
        tokens = np.minimum(z - 1, self.vocab_size - 1).astype(np.int32)
        return {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        step = 0
        while True:
            yield self.batch_at(step)
            step += 1


class MemmapLM:
    """Windows over a flat binary token file (np.uint16/uint32 memmap)."""

    def __init__(self, path: str, batch: int, seq: int, dtype=np.uint16,
                 seed: int = 0):
        self.data = np.memmap(path, dtype=dtype, mode="r")
        self.batch = batch
        self.seq = seq
        self.seed = seed

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, step]))
        max_start = len(self.data) - self.seq - 1
        starts = rng.integers(0, max_start, size=self.batch)
        toks = np.stack([self.data[s:s + self.seq + 1] for s in starts])
        toks = toks.astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def __iter__(self):
        step = 0
        while True:
            yield self.batch_at(step)
            step += 1


class ShardedLoader:
    """Places host batches on the mesh, with background prefetch.

    Traced on the profiler's clock: ``loader/produce`` (``batch_at`` and
    the placement, on the producer thread) and ``loader/wait`` (the
    consumer blocked on the prefetch queue).
    """

    def __init__(self, source, shardings=None, start_step: int = 0,
                 prefetch: int = 2):
        self.source = source
        self.shardings = shardings
        self.step = start_step
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, prefetch))
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._producer, daemon=True)
        self._thread.start()

    def _put(self, batch):
        if self.shardings is not None:
            batch = jax.tree.map(
                lambda x, s: jax.device_put(x, s), batch, self.shardings)
        return batch

    def _producer(self):
        step = self.step
        while not self._stop.is_set():
            with jax.profiler.TraceAnnotation("loader/produce"):
                item = (step, self._put(self.source.batch_at(step)))
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.5)
                    break
                except queue.Full:
                    continue
            step += 1

    def __next__(self):
        with jax.profiler.TraceAnnotation("loader/wait"):
            step, batch = self._q.get()
        self.step = step + 1
        return batch

    def __iter__(self):
        return self

    def close(self):
        self._stop.set()
