"""Multiple-stream execution engine (paper S4.2), adapted to JAX/TPU.

The paper's streaming flow is: partition the workload into tasks; spawn
streams; overlap the H2D stage of task i+1 with the KEX stage of task i.  In
JAX there is no user-visible stream object, so "multiple streams" shows up at
three levels:

  * **Device level** (inside jit): ``stream_map`` partitions the leading axis
    into tasks and executes them as a sequential grid (``lax.map`` /
    ``lax.scan``).  On TPU each task's HBM->VMEM DMA is multi-buffered against
    the previous task's compute by XLA/Mosaic -- exactly the paper's pipeline.
    The ``num_streams`` knob is the task count (pipeline depth).
  * **Host level**: ``HostStreamExecutor`` runs real H2D (``jax.device_put``),
    KEX (a jitted fn) and D2H (``np.asarray``) stages of different tasks
    concurrently on worker threads -- measurable walltime overlap, used by the
    Fig.-9 benchmark.
  * **Cluster level**: grad-accumulation microbatching, chunked-vocab loss and
    chunked prefill reuse ``stream_map`` so collectives/DMA of one chunk
    overlap compute of another.

Dependency handling follows the paper's taxonomy (``repro.core.dependency``):

  * INDEPENDENT      -> plain chunked map.
  * FALSE_DEPENDENT  -> chunk with redundant halo transfer (``repro.core.halo``).
  * TRUE_DEPENDENT   -> carried-state chain / wavefront (``repro.core.wavefront``).
"""

from __future__ import annotations

import concurrent.futures as _futures
import dataclasses
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dependency as dep
from repro.core import halo as halo_lib


# ----------------------------------------------------------------------------
# Device-level streaming (pure JAX, jittable).
# ----------------------------------------------------------------------------


def _split_leading(tree: Any, num_streams: int) -> Any:
    """Reshape every leaf (n, ...) -> (num_streams, n // num_streams, ...)."""

    def _reshape(x: jax.Array) -> jax.Array:
        n = x.shape[0]
        if n % num_streams != 0:
            raise ValueError(
                f"leading axis {n} not divisible by num_streams={num_streams}"
            )
        return x.reshape((num_streams, n // num_streams) + x.shape[1:])

    return jax.tree.map(_reshape, tree)


def _merge_leading(tree: Any) -> Any:
    return jax.tree.map(
        lambda x: x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:]), tree
    )


def stream_map(
    fn: Callable[[Any], Any],
    xs: Any,
    *,
    num_streams: int,
    category: dep.Category = dep.Category.INDEPENDENT,
    halo: int = 0,
    unroll: int = 1,
) -> Any:
    """Partition ``xs`` along axis 0 into ``num_streams`` tasks and pipeline.

    INDEPENDENT: ``fn`` maps a chunk ``(n/num_streams, ...)`` to outputs.
    FALSE_DEPENDENT: each chunk is extended by ``halo`` elements on both sides
      (redundant boundary transfer, paper Fig. 7); ``fn`` receives the haloed
      chunk and must return outputs for the *core* region.
    TRUE_DEPENDENT: use ``stream_scan`` instead (carried state).

    Executed as a sequential task grid: on TPU, task i+1's input DMA overlaps
    task i's compute (the multi-stream pipeline).  ``unroll`` > 1 trades HLO
    size for scheduling freedom.
    """
    if category is dep.Category.TRUE_DEPENDENT:
        raise ValueError("true-dependent workloads need stream_scan (carried state)")
    if not category.streamable:
        raise ValueError(f"category {category} is not streamable (paper S4.1)")

    if category is dep.Category.FALSE_DEPENDENT and halo > 0:
        chunks = halo_lib.halo_partition(xs, num_streams, halo)
        ys = jax.lax.map(fn, chunks)
        return _merge_leading(ys)

    chunks = _split_leading(xs, num_streams)
    ys = jax.lax.map(fn, chunks)
    return _merge_leading(ys)


def batch_schedule(
    costs: Sequence[float], num_streams: int
) -> list[list[int]]:
    """Assign tasks to ``num_streams`` balanced batches (greedy LPT).

    Longest-processing-time-first: sort tasks by descending cost, place each
    on the least-loaded stream.  A generic helper for batching Independent
    tasks (paper §4.1) so no stream drains early — e.g. routing serving
    requests across hosts (ROADMAP: multi-host serving).

    Returns one list of task indices per stream.
    """
    if num_streams < 1:
        raise ValueError(f"num_streams must be >= 1, got {num_streams}")
    lanes: list[list[int]] = [[] for _ in range(num_streams)]
    loads = [0.0] * num_streams
    for i in sorted(range(len(costs)), key=lambda i: -costs[i]):
        j = min(range(num_streams), key=loads.__getitem__)
        lanes[j].append(i)
        loads[j] += costs[i]
    return lanes


def stream_scan(
    fn: Callable[[Any, Any], tuple[Any, Any]],
    init: Any,
    xs: Any,
    *,
    num_streams: int,
    unroll: int = 1,
) -> tuple[Any, Any]:
    """True-dependent streaming: tasks form a RAW chain (paper S4.2, NW-like).

    ``fn(carry, chunk) -> (carry, out_chunk)``.  The carried state serializes
    the *compute* stages, but each chunk's data movement still overlaps the
    previous chunk's compute -- this is exactly how the paper streams NW
    within one diagonal, and how Mamba/SSD chunking passes inter-chunk state.
    """
    chunks = _split_leading(xs, num_streams)
    carry, ys = jax.lax.scan(fn, init, chunks, unroll=unroll)
    return carry, _merge_leading(ys)


# ----------------------------------------------------------------------------
# Host-level streaming: real H2D/KEX/D2H overlap with worker threads.
# ----------------------------------------------------------------------------


@dataclasses.dataclass
class StreamStats:
    """Measured stage times for one run (seconds)."""

    h2d: float = 0.0
    kex: float = 0.0
    d2h: float = 0.0
    wall: float = 0.0

    def stage_times(self):
        from repro.core.rmetric import StageTimes

        return StageTimes(h2d=self.h2d, kex=self.kex, d2h=self.d2h)


class HostStreamExecutor:
    """Execute (H2D -> KEX -> D2H) tasks with ``num_streams`` pipelines.

    This is the closest JAX analogue of hStreams: each stream is a worker that
    moves its task's inputs to the device (``jax.device_put``), dispatches the
    jitted kernel (XLA dispatch is async), and copies results back
    (``np.asarray`` blocks on completion).  With ``num_streams > 1``,
    the H2D of one task runs concurrently with the KEX/D2H of another.

    ``single_stream_run`` executes strictly stage-by-stage (the paper's
    measurement methodology, S3.3) and doubles as the R-measurement harness.
    """

    def __init__(self, fn: Callable[..., Any], *, num_streams: int = 2,
                 device=None, link_bw: float | None = None):
        """``link_bw`` (bytes/s): on hosts whose jax device is zero-copy CPU
        (this container), emulate the accelerator link the paper's platform
        has by sleeping bytes/link_bw during H2D/D2H.  The sleep releases the
        GIL, so it genuinely overlaps with another stream's compute — the
        same physics as a DMA engine.  ``None`` = raw device_put only."""
        self.fn = fn
        self.num_streams = max(1, int(num_streams))
        self.device = device or jax.devices()[0]
        self.link_bw = link_bw

    # -- stage helpers ------------------------------------------------------

    @staticmethod
    def _nbytes(task: Any) -> int:
        return sum(np.asarray(x).nbytes for x in jax.tree.leaves(task))

    def _link_delay(self, task: Any) -> None:
        if self.link_bw:
            time.sleep(self._nbytes(task) / self.link_bw)

    def _h2d(self, host_task: Any) -> Any:
        self._link_delay(host_task)
        moved = jax.device_put(host_task, self.device)
        jax.block_until_ready(moved)
        return moved

    def _kex(self, dev_task: Any) -> Any:
        out = self.fn(dev_task)
        jax.block_until_ready(out)
        return out

    def _d2h(self, dev_out: Any) -> Any:
        out = jax.tree.map(np.asarray, dev_out)
        self._link_delay(out)
        return out

    # -- execution modes ----------------------------------------------------

    def single_stream_run(self, host_tasks: Sequence[Any]) -> tuple[list[Any], StreamStats]:
        """Strictly stage-by-stage (paper S3.3): all H2D, then KEX, then D2H."""
        stats = StreamStats()
        t0 = time.perf_counter()

        t = time.perf_counter()
        dev_tasks = [self._h2d(task) for task in host_tasks]
        stats.h2d = time.perf_counter() - t

        t = time.perf_counter()
        dev_outs = [self._kex(d) for d in dev_tasks]
        stats.kex = time.perf_counter() - t

        t = time.perf_counter()
        outs = [self._d2h(o) for o in dev_outs]
        stats.d2h = time.perf_counter() - t

        stats.wall = time.perf_counter() - t0
        return outs, stats

    def multi_stream_run(self, host_tasks: Sequence[Any]) -> tuple[list[Any], StreamStats]:
        """Pipelined execution: task i+1's H2D overlaps task i's KEX/D2H.

        Per-stage fields of the returned stats are the *cumulative busy
        times* summed over tasks; because the stages overlap, their sum
        normally exceeds ``wall`` — that excess is exactly the hidden
        (overlapped) time the paper's pipeline buys.
        """
        stats = StreamStats()
        results: list[Any] = [None] * len(host_tasks)
        stages = [(0.0, 0.0, 0.0)] * len(host_tasks)
        t0 = time.perf_counter()

        def run_task(i: int, task: Any) -> None:
            s0 = time.perf_counter()
            dev = self._h2d(task)
            s1 = time.perf_counter()
            out = self._kex(dev)
            s2 = time.perf_counter()
            results[i] = self._d2h(out)
            stages[i] = (s1 - s0, s2 - s1, time.perf_counter() - s2)

        with _futures.ThreadPoolExecutor(max_workers=self.num_streams) as pool:
            futs = [pool.submit(run_task, i, t) for i, t in enumerate(host_tasks)]
            for f in futs:
                f.result()

        stats.h2d = sum(s[0] for s in stages)
        stats.kex = sum(s[1] for s in stages)
        stats.d2h = sum(s[2] for s in stages)
        stats.wall = time.perf_counter() - t0
        return results, stats

    def measure_r(self, host_tasks: Sequence[Any]):
        """Run stage-by-stage and return the paper's R (S3.3 methodology)."""
        _, stats = self.single_stream_run(host_tasks)
        return stats.stage_times().ratio(), stats


# ----------------------------------------------------------------------------
# Streaming plan: ties the decision flow together (paper S6's generic flow).
# ----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StreamPlan:
    """Output of the generic flow: decision + strategy + stream count."""

    category: dep.Category
    decision: str
    num_streams: int
    notes: str = ""


def plan_streaming(
    workload: dep.Workload,
    stage_times,
    *,
    max_streams: int = 16,
    halo_elements: int = 0,
    task_elements: int = 1,
) -> StreamPlan:
    """The paper's generic flow (S6): R -> streamable? -> strategy.

    1. Compute R from stage-by-stage times; gate on the necessity band.
    2. Classify the task graph.
    3. For FALSE_DEPENDENT, apply the lavaMD halo-overhead check (S5): if the
       redundant boundary bytes are comparable to the task payload, do not
       stream.
    4. Pick the stream count from the pipeline model.
    """
    from repro.core import rmetric

    decision = rmetric.streaming_decision(stage_times)
    category = dep.classify(workload)

    if decision is not rmetric.StreamDecision.STREAM:
        return StreamPlan(category, decision.value, 1, "R outside the worthwhile band")
    if not category.streamable:
        return StreamPlan(category, "non-streamable", 1, f"{category.value} pattern")

    if category is dep.Category.FALSE_DEPENDENT and halo_elements > 0:
        overhead = halo_lib.halo_overhead_ratio(halo_elements, task_elements)
        if not halo_lib.halo_streaming_profitable(halo_elements, task_elements):
            return StreamPlan(
                category,
                "not-worthwhile",
                1,
                f"halo/task ratio {overhead:.2f} too large (lavaMD case)",
            )

    n = rmetric.optimal_streams(stage_times, max_streams=max_streams)
    return StreamPlan(category, "stream", n, "")
