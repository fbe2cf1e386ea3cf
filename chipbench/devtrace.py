"""Reduce a JAX profiler trace to device busy time, programs and kernels.

``jax.profiler`` writes an ``.xplane.pb``; ``ProfileData`` reads it. Each
TPU is a plane named ``/device:TPU:<n>``. Its ``XLA Modules`` line holds one
event per program run, named ``<jit name>(<fingerprint>)``; its ``XLA Ops``
line one event per operation run, named by the HLO instruction
(``%paged_attention.9 = bf16[...] custom-call(...)``). A ``while`` loop's
event encloses the events of its body. The harness wraps its measured
window in a ``TraceAnnotation`` named ``ANCHOR``, whose event on a host
line gives the traced window and ties the trace's clock to
``time.perf_counter_ns`` (the clock of the engine's spans).

Programs are told apart by the names the trace gives them today
(``PROGRAMS``): the decode step is the jitted lambda of
``ServableModel.decode_fn`` (``jit__lambda``); a fused prefill chunk is
``jit_fn`` of ``ServingEngine._fused_chunk_fn``. The page gather and
scatter of a preemption are also ``jit_fn``, of a handful of operations;
a prefill chunk runs every layer, so it is the ``jit_fn`` run that holds
at least as many operations as the model has layers. The paged-attention
kernel is the operation whose name is ``KERNEL``.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import gzip
import re
from typing import Iterable

ANCHOR = "chipbench_window"
PROGRAMS = {"decode": "jit__lambda", "chunk": "jit_fn"}
KERNEL = "paged_attention"
#: How far the trace's host and device clocks may disagree (ns).
SLACK_NS = 250_000
_MODULE = re.compile(r"\(\d+\)$")
_OP = re.compile(r"^%?([^\s=]+?)(?:\.\d+)* = ")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    t0: int  # ns, trace clock
    t1: int


@dataclasses.dataclass
class DeviceTrace:
    """One device's events inside the traced window (trace clock, ns)."""

    t0: int
    t1: int
    offset_ns: int  # perf_counter_ns = trace ns + offset_ns
    ops: list[Event]
    modules: list[Event]

    def __post_init__(self) -> None:
        self._starts = [o.t0 for o in self.ops]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9


@functools.lru_cache(maxsize=1 << 16)
def op_name(name: str) -> str:
    """``%fusion.12 = f32[...] ...`` -> ``fusion``; a program run's
    ``jit_fn(123)`` -> ``jit_fn``."""
    m = _OP.match(name)
    return m.group(1) if m else _MODULE.sub("", name)


def load(path: str, anchor_perf_ns: int) -> list[DeviceTrace]:
    """One ``DeviceTrace`` per TPU in the trace at ``path`` (gzipped when
    it ends in ``.gz``). ``anchor_perf_ns`` is ``perf_counter_ns()`` read
    as the anchor annotation opened."""
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(str(path))
    anchor = None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANCHOR:
                        anchor = (int(ev.start_ns), int(ev.end_ns))
    if anchor is None:
        raise ValueError(f"no {ANCHOR!r} annotation in {path}")
    lo, hi = anchor
    out = []
    for plane in sorted(devices, key=lambda p: p.name):
        ops, mods = [], []
        for line in plane.lines:
            dst = {"XLA Ops": ops, "XLA Modules": mods}.get(line.name)
            if dst is None:
                continue
            for ev in line.events:
                t0, t1 = int(ev.start_ns), int(ev.end_ns)
                if t0 >= lo and t1 <= hi:
                    dst.append(Event(op_name(ev.name), t0, t1))
        out.append(DeviceTrace(
            t0=lo, t1=hi, offset_ns=anchor_perf_ns - lo,
            ops=sorted(ops, key=lambda e: (e.t0, -e.t1)),
            modules=sorted(mods, key=lambda e: e.t0)))
    return out


def union(evs: Iterable[Event]) -> list[tuple[int, int]]:
    """Merged busy intervals."""
    out: list[list[int]] = []
    for e in sorted(evs, key=lambda e: e.t0):
        if out and e.t0 <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.t1)
        else:
            out.append([e.t0, e.t1])
    return [(a, b) for a, b in out]


def busy_s(dt: DeviceTrace) -> float:
    return sum(b - a for a, b in union(dt.ops)) * 1e-9


def ops_between(dt: DeviceTrace, t0: int, t1: int) -> list[Event]:
    """Operations that start in ``[t0, t1]`` and end by ``t1``."""
    i = bisect.bisect_left(dt._starts, t0)
    j = bisect.bisect_right(dt._starts, t1)
    return [o for o in dt.ops[i:j] if o.t1 <= t1]


def runs_of(dt: DeviceTrace, program: str, n_layers: int) -> list[Event]:
    """Executions of a program (a ``PROGRAMS`` key) in the window."""
    runs = [m for m in dt.modules if m.name == PROGRAMS[program]]
    if program != "chunk":
        return runs
    return [m for m in runs if len(ops_between(dt, m.t0, m.t1)) >= n_layers]


def device_time(evs: Iterable[Event]) -> float:
    return sum(e.t1 - e.t0 for e in evs) * 1e-9


def kernel_events(dt: DeviceTrace, run: Event) -> list[Event]:
    """The paged-attention kernel's runs inside one program run."""
    return [o for o in ops_between(dt, run.t0, run.t1) if o.name == KERNEL]


def spans_in(dt: DeviceTrace, spans, name: str) -> list:
    """Engine spans called ``name`` that lie inside the traced window."""
    lo, hi = dt.t0 + dt.offset_ns, dt.t1 + dt.offset_ns
    return [s for s in spans if s.name == name and lo <= s.t0_ns
            and s.t1_ns <= hi]


def ticks_in(dt: DeviceTrace, spans, tls):
    """(decode tick span, live context of each active row) for the ticks
    inside the traced window. A row's context is its prompt plus the
    tokens it had emitted before the tick (the keys its query sees, its
    own included)."""
    for s in spans_in(dt, spans, "decode_tick"):
        ctxs = []
        for uid in s.args.get("uids") or []:
            tl = tls[uid]
            before = bisect.bisect_left(tl.token_ns, s.t1_ns)
            ctxs.append(tl.prompt_len + before)
        yield s, ctxs


def decode_runs_of_ticks(dt: DeviceTrace, ticks):
    """Pair each decode tick span with the decode program run it
    dispatched and waited for: the run inside the span. The host's and
    the device's clocks agree in the trace to some tens of microseconds,
    so a run may seem to start up to ``SLACK_NS`` before its tick; ticks
    without such a run are left out."""
    decode = [m for m in dt.modules if m.name == PROGRAMS["decode"]]
    starts = [m.t0 for m in decode]
    for span, ctxs in ticks:
        lo, hi = span.t0_ns - dt.offset_ns, span.t1_ns - dt.offset_ns
        i = bisect.bisect_left(starts, lo - SLACK_NS)
        if i < len(decode) and decode[i].t0 < hi \
                and decode[i].t1 <= hi + SLACK_NS:
            yield span, ctxs, decode[i]


def _leaves(dt: DeviceTrace) -> list[Event]:
    """Operations that enclose no other (a ``while`` holds its body's)."""
    ops = dt.ops
    return [o for i, o in enumerate(ops)
            if i + 1 == len(ops) or ops[i + 1].t0 >= o.t1]


def top_ops(dt: DeviceTrace, k: int = 10) -> list[list]:
    """The ``k`` operations that took most device time, each named
    ``<program>/<operation>`` and summed over its runs."""
    mods = dt.modules
    starts = [m.t0 for m in mods]
    tot: dict[str, int] = {}
    for o in _leaves(dt):
        i = bisect.bisect_right(starts, o.t0) - 1
        prog = mods[i].name if i >= 0 and o.t1 <= mods[i].t1 else "?"
        key = f"{prog}/{o.name}"
        tot[key] = tot.get(key, 0) + (o.t1 - o.t0)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in ranked]


def idle_gaps(dt: DeviceTrace, spans, k: int = 10) -> list[list]:
    """The ``k`` longest gaps with no operation on the device, each named
    by the innermost engine span open at its middle (``host_idle`` when
    the engine was in none: the harness waiting for an arrival)."""
    busy = union(dt.ops)
    edges = [dt.t0] + [x for iv in busy for x in iv] + [dt.t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    out = []
    for a, b in gaps:
        mid = (a + b) // 2 + dt.offset_ns
        open_ = [s for s in spans if s.t0_ns <= mid < s.t1_ns]
        name = min(open_, key=lambda s: s.t1_ns - s.t0_ns).name \
            if open_ else "host_idle"
        out.append([name, (b - a) * 1e-9])
    return out
