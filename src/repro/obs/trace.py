"""Ring-buffer span tracer with Chrome/Perfetto trace export.

The paper's stream timelines (Fig. 5 style) are plots of which logical
stream — prefill, decode, transfer — is busy at each instant.  This
module records exactly that: fixed-capacity ring buffer of spans stamped
with ``time.perf_counter_ns()``, one logical *track* per stream, dumped
as Chrome ``trace.json`` (``chrome://tracing`` / https://ui.perfetto.dev)
so the overlap the engine achieves is literally viewable.

Cost model: when ``enabled`` is False every hook is a single attribute
check and the clock is never read (``begin()`` returns 0, ``add()``
returns immediately); no buffer is allocated and no collection is
recorded.  When enabled, a span costs two clock reads, one check that
no profiler trace is recording, and one ``Span`` put in the ring: about
2 µs on the host, no I/O.  The engine only ever calls plain methods on
the tracer, never coerces device values, so instrumentation stays
invisible to the ``@tick_path`` AST lint.

Track names are the span taxonomy's first level:

* ``prefill``  — admission windows and in-flight prefill chunks
* ``decode``   — decode/spec ticks (host_fetch-bounded, so true latency)
* ``transfer`` — page scatter/gather, evict/readmit staging, H2D prep
* ``host``     — what the host loop does inside a tick or an admission
  (``schedule``, ``page_fault``, ``stage``, ``dispatch``, ``fetch_wait``,
  ``emit``) and every Python collection (``gc``)

While a ``jax.profiler`` trace records, each span is also a profiler
annotation of the same name, opened and closed where the span's stamps
are taken, so the host's phases sit on the trace's host plane beside the
device's operations.  The annotation class is looked up in
``sys.modules``: nothing here imports jax, and a process that never
imported it runs no profiler to mirror into.

numpy/stdlib only; importable without jax.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time
import weakref
from dataclasses import dataclass, field
from typing import Any

__all__ = ["Span", "Tracer", "read_trace", "TRACKS"]

#: Logical streams, in display order (tid in the Chrome export).
TRACKS = ("prefill", "decode", "transfer", "host")

#: ``jaxlib``'s profiler annotation class, once jax has been imported.
_TraceMe: Any = None


def _find_traceme() -> Any:
    global _TraceMe
    mod = sys.modules.get("jaxlib._profiler")
    if mod is not None:
        _TraceMe = mod.TraceMe
    return _TraceMe


class _Mark(int):
    """A span's start stamp that also holds the profiler annotation opened
    with it; ``Tracer.add`` closes the annotation.  An annotation whose
    span is never added closes when its mark is freed."""


@dataclass(frozen=True)
class Span:
    """One closed interval on a track. Times are perf_counter nanoseconds."""

    track: str
    name: str
    t0_ns: int
    t1_ns: int
    args: dict[str, Any] = field(default_factory=dict)

    @property
    def dur_ns(self) -> int:
        return self.t1_ns - self.t0_ns

    @property
    def dur_s(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-9


class Tracer:
    """Fixed-capacity span recorder; oldest spans are overwritten.

    Usage on an instrumented path::

        t0 = tr.begin("decode_tick")   # 0 when disabled, never reads clock
        ... work ...
        tr.add("decode", "decode_tick", t0, tick=n, d2h_bytes=b)

    ``add`` closes the span at the current clock; the name given to
    ``begin`` is the profiler annotation's.  ``instant`` records a
    zero-duration marker (used for live STR002 flags).

    An enabled tracer also records every Python collection as a ``gc``
    span on the ``host`` track (``gen``, ``collected``) through one
    ``gc.callbacks`` entry.  The entry holds the tracer weakly and goes
    away when the tracer is freed or closed.
    """

    def __init__(self, capacity: int = 65536, enabled: bool = True) -> None:
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.capacity = capacity
        self.enabled = enabled
        self._buf: list[Span] = []
        self._n = 0  # total spans ever recorded (>= len(_buf) once wrapped)
        self._gc_hook = _gc_hook(self) if enabled else None

    # -- recording -------------------------------------------------------

    def begin(self, name: str) -> int:
        """Span-start stamp; 0 when disabled (callers pass it to ``add``).
        While a profiler trace records, also opens an annotation ``name``."""
        if not self.enabled:
            return 0
        tm = _TraceMe if _TraceMe is not None else _find_traceme()
        if tm is None or not tm.is_enabled():
            return time.perf_counter_ns()
        ann = tm(name)
        mark = _Mark(time.perf_counter_ns())
        mark.ann = ann
        return mark

    def add(self, track: str, name: str, t0_ns: int, **args: Any) -> None:
        if not self.enabled:
            return
        t1_ns = time.perf_counter_ns()
        if type(t0_ns) is _Mark:
            t0_ns.ann.__exit__(None, None, None)
            t0_ns = int(t0_ns)
        self._put(Span(track, name, t0_ns, t1_ns, args))

    def instant(self, track: str, name: str, **args: Any) -> None:
        if not self.enabled:
            return
        now = self.begin(name)
        if type(now) is _Mark:
            now.ann.__exit__(None, None, None)
            now = int(now)
        self._put(Span(track, name, now, now, args))

    def _put(self, span: Span) -> None:
        # Allocates nothing the collector tracks, so no collection (and no
        # ``gc`` span) can start in here: a span is placed whole, before or
        # after the one a collection inside ``Span(...)`` records.
        if len(self._buf) < self.capacity:
            self._buf.append(span)
        else:
            self._buf[self._n % self.capacity] = span
        self._n += 1

    def close(self) -> None:
        """Stop recording collections; spans stay readable."""
        hook, self._gc_hook = self._gc_hook, None
        if hook is not None and hook in gc.callbacks:
            gc.callbacks.remove(hook)

    # -- inspection ------------------------------------------------------

    @property
    def dropped(self) -> int:
        """Spans overwritten by ring wrap-around."""
        return max(0, self._n - self.capacity)

    def spans(self) -> list[Span]:
        """All retained spans, sorted by start time."""
        return sorted(self._buf, key=lambda s: (s.t0_ns, s.t1_ns))

    def clear(self) -> None:
        self._buf.clear()
        self._n = 0

    # -- export ----------------------------------------------------------

    def to_chrome(self, path: str) -> dict[str, Any]:
        """Write Chrome trace-event JSON; returns the written document.

        One process (pid 0, named "repro-serving"), one thread per track.
        Timestamps are microseconds relative to the earliest span so the
        viewer opens at t=0.
        """
        spans = self.spans()
        base = spans[0].t0_ns if spans else 0
        events: list[dict[str, Any]] = [{
            "ph": "M", "pid": 0, "tid": 0, "name": "process_name",
            "args": {"name": "repro-serving"},
        }]
        tids = {tr: i for i, tr in enumerate(TRACKS)}
        for tr in spans:
            tids.setdefault(tr.track, len(tids))
        for tr, tid in sorted(tids.items(), key=lambda kv: kv[1]):
            events.append({"ph": "M", "pid": 0, "tid": tid,
                           "name": "thread_name", "args": {"name": tr}})
        for s in spans:
            ev = {
                "ph": "X",
                "pid": 0,
                "tid": tids[s.track],
                "name": s.name,
                "ts": (s.t0_ns - base) / 1e3,
                "dur": s.dur_ns / 1e3,
                "args": dict(s.args),
            }
            events.append(ev)
        doc = {"traceEvents": events,
               "displayTimeUnit": "ms",
               "otherData": {"dropped_spans": self.dropped}}
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return doc


def _gc_hook(tracer: Tracer):
    """Register a ``gc.callbacks`` entry that records each collection as a
    ``gc`` span on ``tracer``; it holds the tracer weakly and removes
    itself when the tracer is freed."""
    t0 = 0

    def hook(phase: str, info: dict[str, int]) -> None:
        nonlocal t0
        tr = ref()
        if tr is None:
            return
        if phase == "start":
            t0 = tr.begin("gc")
        elif t0:
            tr.add("host", "gc", t0, gen=info["generation"],
                   collected=info["collected"])
            t0 = 0

    # The list itself: the module's name for it is gone at interpreter exit.
    callbacks = gc.callbacks

    def unhook(_ref) -> None:
        if hook in callbacks:
            callbacks.remove(hook)

    ref = weakref.ref(tracer, unhook)
    callbacks.append(hook)
    return hook


def read_trace(path: str) -> list[Span]:
    """Parse a Chrome trace written by :meth:`Tracer.to_chrome` back to spans."""
    with open(path) as f:
        doc = json.load(f)
    names: dict[int, str] = {}
    for ev in doc.get("traceEvents", []):
        if ev.get("ph") == "M" and ev.get("name") == "thread_name":
            names[ev["tid"]] = ev["args"]["name"]
    spans = []
    for ev in doc.get("traceEvents", []):
        if ev.get("ph") != "X":
            continue
        t0 = int(round(ev["ts"] * 1e3))
        spans.append(Span(
            track=names.get(ev["tid"], str(ev["tid"])),
            name=ev["name"],
            t0_ns=t0,
            t1_ns=t0 + int(round(ev["dur"] * 1e3)),
            args=dict(ev.get("args", {})),
        ))
    return sorted(spans, key=lambda s: (s.t0_ns, s.t1_ns))
