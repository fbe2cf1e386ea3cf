"""The host loop's phase spans, the ``gc`` spans and their mirror in a
``jax.profiler`` trace.

Inside every ``decode_tick`` and ``admit`` span the host is always in some
phase (``page_fault``, ``stage``, ``dispatch``, ``fetch_wait``, ``emit``,
``gc``); every collection is a ``gc`` span; and while a profiler trace
records, each span is also a host annotation of the same name at the
span's own stamps."""

import gc
import time

import jax
import numpy as np
import pytest

import repro.configs as C
from repro.models import transformer as T
from repro.obs import Span, Tracer, trace
from repro.runtime.serving import ServeConfig, StreamedBatchEngine

#: Longest stretch of a tick or an admission allowed outside every phase.
GAP_NS = 500_000
#: How far a span's annotation may start from the span's own stamp.
CLOCK_NS = 50_000


@pytest.fixture(scope="module")
def served():
    cfg = C.get_smoke_config("qwen3-4b")
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0))


def _serve(served, tracer, n=4):
    """A paged engine with fused prefill, as the benchmark serves: two
    slots for ``n`` prompts, so admissions run beside decoding slots and
    their chunks interleave decode ticks."""
    cfg, params = served
    scfg = ServeConfig(max_seq=96, prefill_chunk=16, max_new_tokens=6,
                       max_batch=2, paged=True, block_size=16,
                       fused_prefill=True)
    eng = StreamedBatchEngine(cfg, params, scfg, tracer=tracer)
    rng = np.random.default_rng(0)
    for length in (40, 24, 48, 32)[:n]:
        eng.submit(rng.integers(0, cfg.vocab_size, length).astype(np.int32))
    eng.run()
    return eng


def _longest_uncovered(span: Span, covers) -> int:
    """Longest stretch of ``span`` (ns) that no span of ``covers`` holds."""
    ivs = sorted((max(c.t0_ns, span.t0_ns), min(c.t1_ns, span.t1_ns))
                 for c in covers
                 if c.t1_ns > span.t0_ns and c.t0_ns < span.t1_ns)
    worst, at = 0, span.t0_ns
    for a, b in ivs:
        worst = max(worst, a - at)
        at = max(at, b)
    return max(worst, span.t1_ns - at)


def _inside(span: Span, spans) -> list[Span]:
    return [s for s in spans
            if span.t0_ns <= s.t0_ns and s.t1_ns <= span.t1_ns]


def test_phases_cover_every_tick_and_admission(served):
    tr = Tracer()
    eng = _serve(served, tr)
    spans = tr.spans()
    host = [s for s in spans if s.track == "host"]
    ticks = [s for s in spans if s.name == "decode_tick"]
    admits = [s for s in spans if s.name == "admit"]
    assert len(admits) == 4 and len(ticks) == eng.decode_steps
    assert any(_inside(a, ticks) for a in admits)  # ticks ran in admissions
    for tick in ticks:
        n = tick.args["tick"]
        phases = [s for s in _inside(tick, host) if s.name != "gc"]
        assert {s.name for s in phases} == {"stage", "dispatch",
                                            "fetch_wait", "emit"}
        assert all(s.args == {"tick": n} for s in phases)
        assert _longest_uncovered(tick, host) <= GAP_NS, n
    in_ticks = [s for t in ticks for s in _inside(t, spans)]
    for admit in admits:
        uid = admit.args["uid"]
        own = [s for s in _inside(admit, host)
               if s.name != "gc" and "uid" in s.args]
        assert {s.name for s in own} == {"stage", "dispatch", "fetch_wait",
                                         "emit"}
        assert all(s.args == {"uid": uid} for s in own)
        assert all("uid" not in s.args for s in _inside(admit, in_ticks)
                   if s.track == "host")
        assert _longest_uncovered(admit, host) <= GAP_NS, uid


def test_page_faults_and_schedule_carry_their_tick(served):
    tr = Tracer()
    _serve(served, tr)
    spans = tr.spans()
    numbers = {s.args["tick"] for s in spans if s.name == "decode_tick"}
    faults = [s for s in spans if s.name == "page_fault"]
    assert faults and {s.args["tick"] for s in faults} == numbers
    assert all(s.track == "host" and s.args == {}
               for s in spans if s.name == "schedule")
    # A page fault precedes its tick: it ends before the tick starts.
    start = {s.args["tick"]: s.t0_ns for s in spans
             if s.name == "decode_tick"}
    assert all(f.t1_ns <= start[f.args["tick"]] for f in faults)
    assert not any("slot_ids" in s.args for s in spans)


def test_collection_is_a_gc_span():
    tr = Tracer()
    gc.collect()
    spans = [s for s in tr.spans() if s.name == "gc"]
    assert spans and spans[-1].track == "host"
    assert spans[-1].args["gen"] == 2 and spans[-1].args["collected"] >= 0
    assert spans[-1].t1_ns > spans[-1].t0_ns


def test_gc_hook_lives_and_dies_with_its_tracer():
    # Other tracers' hooks may leave while this runs (theirs die in a
    # collection); none may arrive but the enabled tracer's own.
    before = list(gc.callbacks)
    Tracer(enabled=False)
    assert all(cb in before for cb in gc.callbacks)
    tr = Tracer()
    hook = tr._gc_hook
    assert hook in gc.callbacks and hook not in before
    assert all(cb in before or cb is hook for cb in gc.callbacks)
    del tr
    gc.collect()
    assert hook not in gc.callbacks
    closed = Tracer()
    hook = closed._gc_hook
    closed.close()
    assert hook not in gc.callbacks
    gc.collect()
    assert not any(s.name == "gc" for s in closed.spans())


def test_collections_inside_add_keep_the_ring_whole():
    """With a collection at nearly every allocation, ``gc`` spans are
    recorded from inside ``add``; the ring still holds whole spans and
    counts every one."""
    tr = Tracer(capacity=64)
    old = gc.get_threshold()
    gc.set_threshold(1)
    try:
        for i in range(200):
            tr.add("decode", "tick", tr.begin("tick"), i=i)
    finally:
        gc.set_threshold(*old)
    tr.close()
    spans = tr.spans()
    assert len(spans) == 64 and all(isinstance(s, Span) for s in spans)
    n_gc = tr._n - 200
    assert n_gc > 0 and tr.dropped == tr._n - 64
    ticks = [s.args["i"] for s in spans if s.name == "tick"]
    assert ticks == sorted(ticks) and ticks[-1] == 199


class _FakeTraceMe:
    """Stands in for the profiler's annotation: counts what is opened."""
    recording = False
    opened: list = []

    def __init__(self, name):
        self.name = name
        self.closed = False
        _FakeTraceMe.opened.append(self)

    def __exit__(self, *exc):
        self.closed = True

    @staticmethod
    def is_enabled():
        return _FakeTraceMe.recording


@pytest.mark.parametrize("recording", [False, True])
def test_annotation_only_while_a_trace_records(monkeypatch, recording):
    monkeypatch.setattr(trace, "_TraceMe", _FakeTraceMe)
    monkeypatch.setattr(_FakeTraceMe, "recording", recording)
    monkeypatch.setattr(_FakeTraceMe, "opened", [])
    tr = Tracer()
    tr.close()
    t0 = tr.begin("stage")
    assert (type(t0) is int) is not recording
    tr.add("host", "stage", t0, tick=1)
    tr.instant("transfer", "STR002")
    tr.add("decode", "decode_tick", tr.begin("decode_tick"))
    opened = _FakeTraceMe.opened
    if recording:
        assert [a.name for a in opened] == ["stage", "STR002", "decode_tick"]
        assert all(a.closed for a in opened)
    else:
        assert opened == []
    assert all(type(s.t0_ns) is int for s in tr.spans())
    instant = next(s for s in tr.spans() if s.name == "STR002")
    assert instant.t0_ns == instant.t1_ns


def test_disabled_tracer_opens_no_annotation(monkeypatch):
    monkeypatch.setattr(trace, "_TraceMe", _FakeTraceMe)
    monkeypatch.setattr(_FakeTraceMe, "recording", True)
    monkeypatch.setattr(_FakeTraceMe, "opened", [])
    tr = Tracer(enabled=False)
    tr.add("host", "stage", tr.begin("stage"))
    assert _FakeTraceMe.opened == [] and tr.spans() == []


def test_engine_spans_are_profiler_annotations(served, tmp_path):
    """Under ``jax.profiler`` every span the engine records lies on the
    host plane under its own name; mapped to ``perf_counter_ns`` through
    an anchor annotation (as the benchmark's trace reader maps them), the
    annotations start where the spans do."""
    from jax.profiler import ProfileData

    _serve(served, None, n=1)  # compile outside the trace
    tr = Tracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("anchor"):
            anchor_ns = time.perf_counter_ns()
            _serve(served, tr)
            end_ns = time.perf_counter_ns()
    finally:
        jax.profiler.stop_trace()
    spans = [s for s in tr.spans() if anchor_ns <= s.t0_ns
             and s.t1_ns <= end_ns]
    names = {s.name for s in spans}
    assert {"admit", "decode_tick", "prefill_chunk", "stage", "dispatch",
            "fetch_wait", "emit", "page_fault", "schedule"} <= names
    pd = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    starts: dict[str, list[int]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    starts.setdefault(ev.name, []).append(int(ev.start_ns))
    offset = anchor_ns - starts["anchor"][0]
    for name in names:
        want = sorted(s.t0_ns for s in spans if s.name == name)
        got = sorted(t + offset for t in starts.get(name, [])
                     if anchor_ns - CLOCK_NS <= t + offset <= end_ns)
        assert len(got) == len(want), name
        assert max(abs(a - b) for a, b in zip(got, want)) <= CLOCK_NS, name


@pytest.mark.parametrize("paged", [False, True])
def test_unfused_admission_has_the_same_phases(served, paged):
    """Chunks yielded by the servable's prefill generator: one ``dispatch``
    per chunk plus the first token's, and no span left open."""
    cfg, params = served
    scfg = ServeConfig(max_seq=96, prefill_chunk=16, max_new_tokens=4,
                       max_batch=2, paged=paged, block_size=16)
    tr = Tracer()
    eng = StreamedBatchEngine(cfg, params, scfg, tracer=tr)
    rng = np.random.default_rng(1)
    for length in (40, 24, 33):
        eng.submit(rng.integers(0, cfg.vocab_size, length).astype(np.int32))
    eng.run()
    spans = tr.spans()
    host = [s for s in spans if s.track == "host"]
    for admit in (s for s in spans if s.name == "admit"):
        uid, chunks = admit.args["uid"], admit.args["chunks"]
        own = [s for s in _inside(admit, host) if s.args.get("uid") == uid]
        assert sum(s.name == "dispatch" for s in own) == chunks + 1
        assert sum(s.name == "prefill_chunk" and s.args["uid"] == uid
                   for s in spans) == chunks
        assert {s.name for s in own} == {"stage", "dispatch", "fetch_wait",
                                         "emit"}
        assert _longest_uncovered(admit, host) <= GAP_NS, uid
