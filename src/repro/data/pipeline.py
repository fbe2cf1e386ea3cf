"""Data pipeline with host-side multi-stream prefetch.

``PrefetchIterator`` is the paper's H2D/KEX overlap at the training-loop
level (level L1): worker threads produce and transfer the next
``depth`` batches (H2D stage) while the accelerator runs the current step
(KEX stage).  ``depth`` is the stream count; ``depth=0`` degrades to the
paper's single-stream stage-by-stage execution, which is what
``benchmarks/bench_overlap.py`` measures against.

The synthetic token source is deterministic per (seed, step) so restarts
resume identically (fault-tolerance requirement).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterator

import jax
import numpy as np


class SyntheticLM:
    """Deterministic synthetic LM batches (tokens ~ Zipf-ish mixture)."""

    def __init__(
        self,
        vocab_size: int,
        *,
        global_batch: int,
        seq_len: int,
        seed: int = 0,
        extra: dict[str, tuple[tuple[int, ...], Any]] | None = None,
        work_ms: float = 0.0,
    ):
        self.vocab_size = vocab_size
        self.global_batch = global_batch
        self.seq_len = seq_len
        self.seed = seed
        self.extra = extra or {}
        self.work_ms = work_ms  # simulated host preprocessing cost

    def batch_at(self, step: int) -> dict[str, np.ndarray]:
        rng = np.random.default_rng((self.seed, step))
        if self.work_ms > 0:  # simulate tokenization / decoding cost
            t_end = time.perf_counter() + self.work_ms / 1e3
            while time.perf_counter() < t_end:
                pass
        # mixture of a low-entropy head and uniform tail, roughly zipfian
        head = rng.integers(0, max(2, self.vocab_size // 64),
                            size=(self.global_batch, self.seq_len))
        tail = rng.integers(0, self.vocab_size,
                            size=(self.global_batch, self.seq_len))
        pick = rng.random((self.global_batch, self.seq_len)) < 0.7
        batch = {"tokens": np.where(pick, head, tail).astype(np.int32)}
        for name, (shape, dtype) in self.extra.items():
            batch[name] = (0.1 * rng.standard_normal(
                (self.global_batch,) + tuple(shape))).astype(dtype)
        return batch

    def __iter__(self) -> Iterator[dict[str, np.ndarray]]:
        step = 0
        while True:
            yield self.batch_at(step)
            step += 1


class PrefetchIterator:
    """Multi-stream host->device prefetch (the paper's pipeline).

    ``depth`` worker slots fetch + ``device_put`` upcoming batches while the
    consumer computes: H2D(t+1..t+depth) overlaps KEX(t).
    """

    def __init__(
        self,
        source: Iterator[dict[str, np.ndarray]],
        *,
        depth: int = 2,
        put: Callable[[Any], Any] | None = None,
        start_step: int = 0,
    ):
        self.source = source
        self.depth = max(0, depth)
        self.put = put if put is not None else jax.device_put
        self._q: queue.Queue = queue.Queue(maxsize=max(1, self.depth))
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._started = False
        # skip batches consumed before a restart (deterministic resume)
        for _ in range(start_step):
            next(self.source)

    def _worker(self) -> None:
        try:
            for batch in self.source:
                if self._stop.is_set():
                    return
                dev = self.put(batch)  # the H2D stage of this stream
                self._q.put(dev)
        except StopIteration:
            pass
        finally:
            self._q.put(None)

    def __iter__(self):
        return self

    def __next__(self):
        if self.depth == 0:  # single-stream: fetch + transfer synchronously
            batch = next(self.source)
            return self.put(batch)
        if not self._started:
            self._thread = threading.Thread(target=self._worker, daemon=True)
            self._thread.start()
            self._started = True
        item = self._q.get()
        if item is None:
            raise StopIteration
        return item

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            while not self._q.empty():
                self._q.get_nowait()
