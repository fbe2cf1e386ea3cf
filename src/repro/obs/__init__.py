"""Observability layer: trace spans, metrics, and overlap reconstruction.

``Tracer`` records per-tick spans on the prefill/decode/transfer tracks,
the host loop's phases and Python collections on the host track
(Chrome/Perfetto export, mirrored into a recording ``jax.profiler``
trace); ``MetricsRegistry`` holds the engine's
counters and latency/transfer histograms behind one snapshot; ``overlap``
turns the recorded timeline into a measured overlap efficiency and
compares it with the R-gate's analytic prediction.  On top of those:
``requests`` rebuilds per-request lifecycles (queue wait, TTFT,
per-token ITLs, stalls) from a trace, ``slo`` scores them against
TTFT/ITL targets (attainment + goodput), ``doctor`` turns a trace into a
ranked bottleneck diagnosis, and ``baseline`` gates fresh bench results
against the committed ``BENCH_*.json``.

Everything here is numpy/stdlib-importable — no jax at import time — so
the runtime and analysis layers can depend on it freely.
"""

from .metrics import Histogram, MetricsRegistry, SCHEMA_VERSION
from .overlap import (
    measured_overlap,
    overlap_report,
    predicted_overlap,
    stage_times_from_trace,
)
from .requests import (
    RequestTimeline,
    reconstruct_timelines,
    timeline_aggregates,
    timelines_from_trace,
)
from .slo import SLOPolicy, score_timelines
from .trace import TRACKS, Span, Tracer, read_trace

__all__ = [
    "Histogram",
    "MetricsRegistry",
    "SCHEMA_VERSION",
    "Span",
    "Tracer",
    "TRACKS",
    "read_trace",
    "measured_overlap",
    "predicted_overlap",
    "overlap_report",
    "stage_times_from_trace",
    "RequestTimeline",
    "reconstruct_timelines",
    "timelines_from_trace",
    "timeline_aggregates",
    "SLOPolicy",
    "score_timelines",
]
