"""The reduction from a profiler trace to device metrics: on synthetic
events worked by hand, and on a small trace recorded on a TPU v5e
(``record_trace.py``: the tiny configuration of ``testdata/`` served
through the harness), whose readings on the chip it must give again."""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

from chipbench import devtrace, harness, timelines, traffic  # noqa: E402
from chipbench.devtrace import DeviceTrace, Event  # noqa: E402
from chipbench.dims import dims_of, load_config  # noqa: E402

DATA = ROOT / "chipbench" / "testdata"


def test_op_names():
    assert devtrace.op_name(
        "%paged_attention.9 = bf16[32,8,4,128]{3,2,1,0} custom-call(s32[32,"
        "256] %copy-done)") == "paged_attention"
    assert devtrace.op_name(
        "%bitcast_dynamic-update-slice_fusion.4 = bf16[36] fusion(x)") == \
        "bitcast_dynamic-update-slice_fusion"
    assert devtrace.op_name("%while.5 = (s32[]) while(%t)") == "while"
    assert devtrace.op_name("jit__lambda(1810128628140581707)") == \
        "jit__lambda"


def _synthetic():
    # Window 0..1000 ns, 2 layers. One decode run 100..400 holding a while
    # loop whose body is two kernel calls and a fusion; a one-op page copy
    # (jit_fn) 500..560; a prefill chunk (jit_fn) of 4 ops 600..900.
    mods = [Event("jit__lambda", 100, 400), Event("jit_fn", 500, 560),
            Event("jit_fn", 600, 900)]
    ops = [Event("while", 110, 390), Event("paged_attention", 110, 200),
           Event("fusion", 200, 300), Event("paged_attention", 300, 390),
           Event("gather", 500, 560),
           Event("fusion", 600, 700), Event("copy", 700, 720),
           Event("copy", 720, 740), Event("fusion", 740, 900)]
    return DeviceTrace(t0=0, t1=1000, offset_ns=10_000, ops=ops,
                       modules=mods)


def test_busy_runs_and_kernels():
    dt = _synthetic()
    assert devtrace.busy_s(dt) == pytest.approx((280 + 60 + 300) * 1e-9)
    decode = devtrace.runs_of(dt, "decode", n_layers=2)
    assert decode == [Event("jit__lambda", 100, 400)]
    # A chunk runs every layer, a page copy a handful of operations.
    assert devtrace.runs_of(dt, "chunk", n_layers=2) == [
        Event("jit_fn", 600, 900)]
    assert [e.t0 for e in devtrace.kernel_events(dt, decode[0])] == [110, 300]


def test_top_ops_count_leaves_by_program():
    top = dict(devtrace.top_ops(_synthetic()))
    assert "jit__lambda/while" not in top
    assert top["jit__lambda/paged_attention"] == pytest.approx(180e-9)
    assert top["jit_fn/fusion"] == pytest.approx(260e-9)
    assert top["jit_fn/copy"] == pytest.approx(40e-9)
    assert top["jit_fn/gather"] == pytest.approx(60e-9)


class _Span:
    def __init__(self, name, t0, t1):
        self.name, self.t0_ns, self.t1_ns, self.args = name, t0, t1, {}


def test_idle_gaps_are_named_by_the_open_span():
    dt = _synthetic()
    spans = [_Span("decode_tick", 10_100, 10_400),
             _Span("admit", 10_420, 10_990)]
    gaps = devtrace.idle_gaps(dt, spans, k=3)
    # Gaps 0..110, 390..500, 900..1000 and 560..600 (shortest, left out);
    # the spans are on the host clock, 10,000 ns ahead of the trace's.
    assert [g[0] for g in gaps] == ["host_idle", "admit", "admit"]
    assert [g[1] for g in gaps] == pytest.approx([110e-9, 110e-9, 100e-9])


# -- a trace recorded on the chip ---------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    rec = json.loads((DATA / "tiny_trace.json").read_text())
    traces = devtrace.load(DATA / "tiny_trace.xplane.pb.gz", rec["anchor_ns"])
    conf = load_config(DATA / "tiny.json")
    spans = [harness_span(*s) for s in rec["spans"]]
    cell = harness.Cell(
        name="tiny.open", root=ROOT, conf=conf, dims=dims_of(conf),
        mix=traffic.load_mix(DATA / "tiny_open.json"), chips=1,
        end_to_end=[], per_layer=[])
    run = harness.Run(
        cell=cell, seed=rec["seed"], seconds=rec["seconds"], setup_s=0.0,
        t0_ns=rec["t0_ns"], t1_ns=rec["t1_ns"], spans=spans,
        timelines=timelines.reconstruct(spans), due_ns={}, window_uids=[],
        refused=0, counters0={}, counters1={}, compiles_in_window=(0, 0),
        submit_lag_s=[], peaks=rec["peaks"], traces=traces)
    return rec, run


def harness_span(track, name, t0, t1, args):
    from repro.obs.trace import Span

    return Span(track, name, t0, t1, args)


def test_recorded_trace_is_from_a_tpu(recorded):
    rec, run = recorded
    assert rec["device"]["platform"] == "tpu"
    assert rec["device"]["kind"] == "TPU v5 lite"
    dt = run.traces[0]
    assert len(run.traces) == 1 and len(dt.ops) == rec["n_ops"] > 0
    assert len(dt.modules) == rec["n_modules"]
    assert dt.window_s == pytest.approx(rec["window_s"])
    assert 0 < devtrace.busy_s(dt) == pytest.approx(rec["busy_s"])
    assert devtrace.busy_s(dt) < dt.window_s


def test_recorded_programs_and_kernel_are_found(recorded):
    _, run = recorded
    dt, layers = run.traces[0], run.cell.dims.n_layers
    decode = devtrace.runs_of(dt, "decode", layers)
    chunks = devtrace.runs_of(dt, "chunk", layers)
    assert decode and chunks
    for r in decode:
        assert len(devtrace.kernel_events(dt, r)) == layers
    for r in chunks:
        assert not devtrace.kernel_events(dt, r)
    ticks = list(devtrace.decode_runs_of_ticks(
        dt, devtrace.ticks_in(dt, run.spans, run.timelines)))
    assert len(ticks) >= len(decode) - 2  # the window may cut one at each end


def _same(got, want):
    assert [n for n, _ in got] == [n for n, _ in want]
    assert [v for _, v in got] == pytest.approx([v for _, v in want])


def test_recorded_readings_repeat(recorded):
    rec, run = recorded
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    from_trace = {m["name"] for m in bench["per_layer"]
                  if m["source"] == "device_trace"}
    assert from_trace <= set(rec["metrics"])
    for name in from_trace:
        want = rec["metrics"][name]
        assert harness.reader(ROOT, name)(run) == pytest.approx(want), name
    dt = run.traces[0]
    _same(devtrace.top_ops(dt), rec["breakdown"]["device_ops"])
    _same(devtrace.idle_gaps(dt, run.spans), rec["breakdown"]["idle_gaps"])


def test_recorded_roofline_and_mfu_are_shares(recorded):
    rec, _ = recorded
    for name in ("paged_attn_roofline", "mfu", "device_idle.open"):
        assert 0 < rec["metrics"][name] < 100, name
