"""The host loop's readers: ``host_ms_per_tick`` and ``gc_pause_ms``, on
spans built by hand and on a traced run of the tiny cell (``minicell``)
on the CPU."""

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax  # noqa: E402
import pytest  # noqa: E402

from chipbench import harness  # noqa: E402
from minicell import CPU_PEAKS, ROOT, make_root  # noqa: E402
from repro.obs import Span  # noqa: E402

MS = 1_000_000  # ns


def _run(spans, t0_ns=10 * MS, t1_ns=100 * MS):
    return harness.Run(
        cell=None, seed=1, seconds=(t1_ns - t0_ns) * 1e-9, setup_s=1.0,
        t0_ns=t0_ns, t1_ns=t1_ns, spans=spans, timelines={}, due_ns={},
        window_uids=[], refused=0, counters0={}, counters1={},
        compiles_in_window=(0, 0), submit_lag_s=[], peaks={})


def _tick(n, t0):
    """A tick ``n`` at ``t0`` ms: a page fault of 1 ms before it, then
    stage 1, dispatch 2, fetch_wait 30 and emit 3 ms inside it."""
    ms = lambda a, b: (int((t0 + a) * MS), int((t0 + b) * MS))  # noqa: E731
    phases = [("page_fault", -1, 0), ("stage", 0, 1), ("dispatch", 1, 3),
              ("fetch_wait", 3, 33), ("emit", 33, 36)]
    return ([Span("decode", "decode_tick", *ms(0, 36), {"tick": n})]
            + [Span("host", name, *ms(a, b), {"tick": n})
               for name, a, b in phases])


def _spans():
    spans = _tick(1, 5) + _tick(2, 45) + _tick(3, 90) + _tick(4, 130)
    spans += [
        # An admission's phases carry its uid, not a tick: not counted.
        Span("host", "stage", 41 * MS, 44 * MS, {"uid": 7}),
        # A collection inside tick 2's emit, one between ticks, one after
        # the window: only those starting in [10, 100) ms count.
        Span("host", "gc", 79 * MS, 80 * MS, {"gen": 0, "collected": 0}),
        Span("host", "gc", 82 * MS, 86 * MS, {"gen": 2, "collected": 9}),
        Span("host", "gc", 120 * MS, 190 * MS, {"gen": 2, "collected": 1}),
        Span("host", "schedule", 87 * MS, 88 * MS, {}),
    ]
    return spans


@pytest.mark.parametrize("metric,want", [
    # Ticks 2 and 3 start in the window; each has 1 + 1 + 2 + 3 ms of host
    # phases besides its 30 ms wait for the device.
    ("host_ms_per_tick.open", 7.0),
    ("host_ms_per_tick.offline", 7.0),
    ("gc_pause_ms.open", 5.0),
    ("gc_pause_ms.offline", 5.0),
])
def test_readers_on_hand_built_spans(metric, want):
    assert harness.reader(ROOT, metric)(_run(_spans())) == pytest.approx(
        want)


def test_no_collection_reads_zero():
    spans = [s for s in _spans() if s.name != "gc"]
    assert harness.reader(ROOT, "gc_pause_ms")(_run(spans)) == 0.0


@pytest.mark.parametrize("metric", ["host_ms_per_tick", "gc_pause_ms"])
def test_a_program_without_host_phases_reads_nothing(metric):
    """A program that records no ``host`` track (the benchmark's readers
    laid over an older checkout) gives no value, and does not raise."""
    spans = [s for s in _spans() if s.track != "host"]
    assert harness.reader(ROOT, metric)(_run(spans)) is None


def test_traced_rehearsal_reads_the_host_loop(tmp_path):
    """The tiny cell, traced under ``jax.profiler`` on the CPU: the window
    has host phases for its ticks, and both readers give a value."""
    root = make_root(tmp_path)
    cell = harness.load_cell(root, "mini.burst")
    kept: dict = {}
    out = harness.run_cell(
        cell, 2 ** 35 + 5, 1.5, trace=True,
        process_t0_ns=time.perf_counter_ns(), clock=harness.CompileClock(),
        log=lambda m: None,
        peak_table={jax.devices()[0].device_kind: CPU_PEAKS}, keep=kept)
    assert out["correct"] is True and out["failed"] == 0
    run = kept["run"]
    host = harness.reader(root, "host_ms_per_tick")(run)
    ticks = [s for s in run.spans if s.name == "decode_tick"
             and run.t0_ns <= s.t0_ns < run.t1_ns]
    assert ticks and host > 0
    assert harness.reader(root, "gc_pause_ms")(run) >= 0.0
