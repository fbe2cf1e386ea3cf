"""Observability layer: trace ring buffer, metrics histograms, overlap
reconstruction from the recorded timeline, runtime transfer accounting
(live STR002), and the tracer's zero-interference contract with the
serving engine (bitwise token parity, bounded overhead)."""

import gc
import json

import jax
import numpy as np
import pytest

import repro.configs as C
from repro.analysis.budget import TransferBudget
from repro.core import rmetric
from repro.models import transformer as T
from repro.obs import (Histogram, MetricsRegistry, SCHEMA_VERSION, Span,
                       Tracer, measured_overlap, overlap_report,
                       predicted_overlap, read_trace, stage_times_from_trace)
from repro.runtime.serving import ServeConfig, StreamedBatchEngine

# ---------------------------------------------------------------------------
# metrics


class TestHistogram:
    def test_quantiles_geometric_buckets(self):
        h = Histogram()
        for v in range(1, 101):
            h.observe(v * 1e-3)
        s = h.snapshot()
        assert s["count"] == 100
        assert s["min"] == pytest.approx(1e-3)
        assert s["max"] == pytest.approx(0.1)
        # bucket growth is 8%; quantiles land within one bucket of truth
        assert s["p50"] == pytest.approx(50e-3, rel=0.1)
        assert s["p99"] == pytest.approx(99e-3, rel=0.1)
        assert s["mean"] == pytest.approx(50.5e-3, rel=1e-6)

    def test_quantile_clamped_to_observed_range(self):
        h = Histogram()
        h.observe(3.0)
        assert h.quantile(0.0) == h.quantile(1.0) == pytest.approx(3.0)

    def test_empty_snapshot_is_zeros(self):
        s = Histogram().snapshot()
        assert s == {"count": 0, "sum": 0.0, "mean": 0.0, "min": 0.0,
                     "max": 0.0, "p50": 0.0, "p99": 0.0}


class TestMetricsRegistry:
    def test_counters(self):
        m = MetricsRegistry()
        m.inc("a")
        m.inc("a", 4)
        m.set_value("b", 7)
        m.max_value("b", 3)  # lower: no-op
        m.max_value("b", 9)
        assert m.value("a") == 5 and m.value("b") == 9
        assert m.value("missing", -1) == -1

    def test_snapshot_schema(self):
        m = MetricsRegistry()
        m.inc("x")
        m.observe("lat", 0.5)
        s = m.snapshot()
        assert s["schema"] == SCHEMA_VERSION
        assert s["counters"] == {"x": 1}
        assert set(s["histograms"]) == {"lat"}
        assert s["histograms"]["lat"]["count"] == 1


# ---------------------------------------------------------------------------
# trace


@pytest.fixture
def no_collections():
    """No Python collection (so no ``gc`` span) while the test runs."""
    gc.disable()
    try:
        yield
    finally:
        gc.enable()


class TestTracer:
    def test_disabled_records_nothing(self):
        tr = Tracer(enabled=False)
        assert tr.begin("tick") == 0
        tr.add("decode", "tick", tr.begin("tick"))
        tr.instant("transfer", "STR002")
        assert tr.spans() == [] and tr.dropped == 0

    def test_ring_overwrites_oldest(self, no_collections):
        tr = Tracer(capacity=4)
        for i in range(6):
            t0 = tr.begin(f"s{i}")
            tr.add("decode", f"s{i}", t0)
        spans = tr.spans()
        assert len(spans) == 4 and tr.dropped == 2
        assert [s.name for s in spans] == ["s2", "s3", "s4", "s5"]

    def test_chrome_round_trip(self, tmp_path, no_collections):
        tr = Tracer()
        t0 = tr.begin("admit")
        tr.add("prefill", "admit", t0, uid=1, chunks=2)
        tr.add("decode", "decode_tick", tr.begin("decode_tick"), tick=0)
        tr.instant("transfer", "STR002", tick=0)
        path = tmp_path / "trace.json"
        doc = tr.to_chrome(str(path))
        on_disk = json.loads(path.read_text())
        assert on_disk == doc
        evs = [e for e in on_disk["traceEvents"] if e["ph"] == "X"]
        assert len(evs) == 3
        back = read_trace(str(path))
        assert [s.name for s in back] == [s.name for s in tr.spans()]
        for got, want in zip(back, tr.spans()):
            # µs round trip: durations survive within rounding
            assert abs(got.dur_ns - want.dur_ns) <= 1_000
            assert got.args == {k: v for k, v in want.args.items()}


# ---------------------------------------------------------------------------
# overlap


def _ms(x):
    return int(x * 1e6)  # ms -> ns


class TestOverlap:
    def test_measured_overlap_synthetic(self):
        spans = [
            Span("decode", "decode_tick", _ms(0), _ms(20), {}),
            Span("transfer", "h2d_stage", _ms(5), _ms(15), {}),   # hidden
            Span("transfer", "evict", _ms(25), _ms(35), {}),      # exposed
        ]
        m = measured_overlap(spans)
        assert m["total_s"] == pytest.approx(20e-3)
        assert m["hidden_s"] == pytest.approx(10e-3)
        assert m["efficiency"] == pytest.approx(0.5)

    def test_measured_overlap_no_transfer(self):
        m = measured_overlap([Span("decode", "t", 0, 10, {})])
        assert m["total_s"] == 0.0 and m["efficiency"] == 0.0

    def test_predicted_overlap_follows_r_gate(self):
        balanced = rmetric.StageTimes(h2d=1.0, kex=1.0, d2h=1.0)
        p = predicted_overlap(balanced)
        assert p["decision"] == rmetric.StreamDecision.STREAM.value
        assert 0.0 < p["efficiency"] <= 1.0 and p["n_streams"] > 1
        compute_bound = rmetric.StageTimes(h2d=1e-3, kex=1.0, d2h=1e-3)
        q = predicted_overlap(compute_bound)
        assert q["decision"] == rmetric.StreamDecision.NOT_WORTHWHILE.value
        assert q["efficiency"] == 0.0

    def test_overlap_report_gap(self):
        spans = [
            Span("decode", "decode_tick", _ms(0), _ms(20), {}),
            Span("transfer", "h2d_stage", _ms(5), _ms(15), {}),
        ]
        rep = overlap_report(
            spans, stage_times=rmetric.StageTimes(h2d=1.0, kex=1.0, d2h=1.0),
            category="independent")
        assert {"measured", "predicted", "gap", "category"} <= set(rep)
        assert rep["gap"] == pytest.approx(
            rep["measured"]["efficiency"] - rep["predicted"]["efficiency"])

    def test_stage_times_from_trace_synthetic(self):
        spans = []
        for i in range(3):
            base = _ms(100 * i)
            spans.append(Span("prefill", "admit", base, base + _ms(40),
                              {"uid": i, "chunks": 2}))
            spans.append(Span("decode", "decode_tick", base + _ms(10),
                              base + _ms(20), {}))
        st = stage_times_from_trace(spans)
        assert st is not None
        # (40ms admit - 10ms contained decode) / 2 chunks = 15ms
        assert st.h2d == pytest.approx(15e-3)
        assert st.kex == pytest.approx(10e-3)
        assert stage_times_from_trace(spans[:2]) is None  # too few samples


# ---------------------------------------------------------------------------
# engine integration


@pytest.fixture(scope="module")
def served():
    cfg = C.get_smoke_config("qwen3-4b")
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0))


def _prompts(cfg, lens, seed=1):
    return [np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed + i), (n,), 0, cfg.vocab_size))
        for i, n in enumerate(lens)]


def _scfg(**kw):
    base = dict(max_seq=64, prefill_chunk=16, max_new_tokens=5, max_batch=2)
    base.update(kw)
    return ServeConfig(**base)


MODES = {
    "contiguous": {},
    "paged": {"paged": True, "block_size": 16},
    "paged_sharing": {"paged": True, "block_size": 16,
                      "prefix_sharing": True},
    # generations long enough for the n-gram drafter to start hitting —
    # short runs fall back to plain ticks and never record a spec_tick
    "paged_spec": {"paged": True, "block_size": 16, "spec_decode": True,
                   "spec_k": 4, "max_seq": 96, "max_new_tokens": 12},
}


def _mode_prompts(cfg, mode):
    if mode == "paged_sharing":  # page-aligned shared system prefix
        head = _prompts(cfg, [16], seed=50)[0]
        return [np.concatenate([head, t])
                for t in _prompts(cfg, [8, 16, 24], seed=60)]
    if mode == "paged_spec":
        return _prompts(cfg, [24, 32, 40, 16], seed=3)
    return _prompts(cfg, [24, 32, 16], seed=7)


class TestEngineTelemetry:
    @pytest.mark.parametrize("mode", sorted(MODES))
    def test_tracing_is_invisible_to_tokens(self, served, mode):
        """Greedy outputs are bitwise identical with tracing on and off,
        and the traced run records a non-empty timeline."""
        cfg, params = served
        prompts = _mode_prompts(cfg, mode)
        outs = {}
        for tr in (None, Tracer()):
            eng = StreamedBatchEngine(cfg, params, _scfg(**MODES[mode]),
                                      tracer=tr)
            uids = [eng.submit(p) for p in prompts]
            out = eng.run()
            outs[tr is not None] = [out[u] for u in uids]
        for a, b in zip(outs[False], outs[True]):
            np.testing.assert_array_equal(a, b)
        spans = eng.obs.spans()
        assert spans and eng.obs.dropped == 0
        names = {s.name for s in spans}
        assert "admit" in names and "h2d_stage" in names
        assert ("spec_tick" if mode == "paged_spec" else
                "decode_tick") in names
        assert all(s.t1_ns >= s.t0_ns for s in spans)

    def test_untraced_engine_records_nothing(self, served):
        cfg, params = served
        eng = StreamedBatchEngine(cfg, params, _scfg())
        eng.submit(_prompts(cfg, [16])[0])
        eng.run()
        assert not eng.obs.enabled and eng.obs.spans() == []
        assert eng.decode_steps > 0  # counters still live without tracing

    def test_counter_shims_route_through_registry(self, served):
        """The legacy counter attributes (tests/benches read AND reset
        them) are views over the metrics registry."""
        cfg, params = served
        eng = StreamedBatchEngine(cfg, params, _scfg())
        eng.submit(_prompts(cfg, [24])[0])
        eng.run()
        assert eng.decode_steps == eng.metrics.value("serving.decode_steps")
        assert eng.admissions == eng.metrics.value("serving.admissions") == 1
        eng.decode_steps = 0  # the profiler's reset idiom
        assert eng.metrics.value("serving.decode_steps") == 0
        eng.metrics.inc("serving.decode_steps", 3)
        assert eng.decode_steps == 3

    def test_metrics_snapshot_schema(self, served):
        cfg, params = served
        eng = StreamedBatchEngine(cfg, params,
                                  _scfg(paged=True, block_size=16),
                                  tracer=Tracer())
        for p in _prompts(cfg, [24, 16], seed=3):
            eng.submit(p)
        eng.run()
        s = eng.metrics_snapshot()
        assert s["schema"] == SCHEMA_VERSION
        assert s["counters"]["serving.decode_steps"] > 0
        assert s["counters"]["transfer.d2h_bytes"] > 0
        for h in ("latency.ttft_s", "latency.itl_s",
                  "transfer.d2h_bytes_per_tick"):
            assert s["histograms"][h]["count"] > 0
            assert s["histograms"][h]["p99"] >= s["histograms"][h]["p50"]
        d = s["derived"]
        assert d["tokens_per_s"] > 0
        pool = d["pool"]  # drained after run(): in_use 0, peak pinned
        assert 0 == pool["in_use"] < pool["peak_in_use"] <= pool["capacity"]
        json.dumps(s)  # the whole snapshot must be JSON-serializable

    def test_live_str002_on_overfetch(self, served):
        """Runtime transfer accounting: a tick fetching more bytes than its
        declared @transfer_budget raises the live STR002 signal (warning +
        counter + instant span) when tracing is on."""
        cfg, params = served
        eng = StreamedBatchEngine(cfg, params, _scfg(), tracer=Tracer())
        # shrink the declared budget under the honest 4 B/slot fetch
        eng._decode_budget = TransferBudget(1, (0,), 1)
        eng.submit(_prompts(cfg, [16])[0])
        with pytest.warns(RuntimeWarning, match="STR002"):
            eng.run()
        assert eng.metrics.value("analysis.str002_live") > 0
        flagged = [s for s in eng.obs.spans() if s.name == "STR002"]
        assert flagged and flagged[0].track == "transfer"
        assert flagged[0].args["d2h_bytes"] > flagged[0].args["limit"]

    def test_honest_ticks_stay_under_budget(self, served):
        """The shipped decode/verify budgets are exact: tracing a clean run
        never trips the live gate."""
        cfg, params = served
        eng = StreamedBatchEngine(
            cfg, params,
            _scfg(paged=True, block_size=16, spec_decode=True, spec_k=3),
            tracer=Tracer())
        for p in _prompts(cfg, [24, 16], seed=11):
            eng.submit(p)
        eng.run()
        assert eng.metrics.value("analysis.str002_live") == 0

    def test_accounting_off_without_tracer(self, served):
        cfg, params = served
        eng = StreamedBatchEngine(cfg, params, _scfg())
        eng._decode_budget = TransferBudget(1, (0,), 1)
        eng.submit(_prompts(cfg, [16])[0])
        eng.run()
        assert eng.metrics.value("analysis.str002_live") == 0

    def test_profiler_consumes_trace(self, served):
        """profile_engine prefers production stage times reconstructed from
        the live trace over fresh synthetic probes."""
        from repro.tuning.profiler import profile_engine
        cfg, params = served
        eng = StreamedBatchEngine(cfg, params, _scfg(), tracer=Tracer())
        for p in _prompts(cfg, [24, 32, 16], seed=5):
            eng.submit(p)
        eng.run()
        st = stage_times_from_trace(eng.obs.spans())
        assert st is not None and st.h2d > 0 and st.kex > 0
        prof = profile_engine(eng, 24)
        assert prof.chunk_s == pytest.approx(st.h2d)
        assert prof.decode_s == pytest.approx(st.kex)


# ---------------------------------------------------------------------------
# slow tier: overhead guard + zoo overlap sweep


@pytest.mark.slow
def test_trace_overhead_guard(served):
    """Tracing must cost < 5% tokens/s (a span is one clock read and one
    tuple append).  Median-of-5 interleaved runs to damp host jitter."""
    import time
    cfg, params = served
    scfg = _scfg(paged=True, block_size=16)
    prompts = _prompts(cfg, [24, 32, 16, 24], seed=13)

    def build(tr):
        eng = StreamedBatchEngine(cfg, params, scfg, tracer=tr)
        eng.submit(prompts[0])
        eng.run()  # compile warmup
        return eng

    engines = {False: build(None), True: build(Tracer())}
    walls = {False: [], True: []}
    for _ in range(5):
        for traced, eng in engines.items():
            if traced:
                eng.obs.clear()
            t0 = time.perf_counter()
            for p in prompts:
                eng.submit(p)
            eng.run()
            walls[traced].append(time.perf_counter() - t0)
    ratio = float(np.median(walls[False]) / np.median(walls[True]))
    assert ratio >= 0.95, f"tracing overhead too high: {ratio:.3f}x"


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["contiguous", "paged"])
@pytest.mark.parametrize("arch", sorted(C.list_archs()))
def test_zoo_obs_overlap(arch, mode):
    """Nightly sweep: every servable zoo config yields a coherent traced
    timeline — measured overlap in [0, 1], a valid metrics snapshot, and
    no live budget violations — in both KV layouts."""
    cfg = C.get_smoke_config(arch)
    scfg = ServeConfig(max_seq=64, prefill_chunk=16, max_new_tokens=4,
                       max_batch=2,
                       **({"paged": True, "block_size": 16}
                          if mode == "paged" else {}))
    if cfg.prefix_len:  # prefix-LM archs fall back to the sequential engine
        pytest.skip("prefix-LM archs are not streamed")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = StreamedBatchEngine(cfg, params, scfg, tracer=Tracer())
    rng = np.random.default_rng(0)
    kw = {}
    if cfg.is_encoder_decoder:
        kw["enc_inputs"] = rng.standard_normal(
            (cfg.encoder_seq, cfg.d_model)).astype(np.float32)
    for n in (24, 16):
        eng.submit(rng.integers(0, cfg.vocab_size, size=n).astype(np.int32),
                   **kw)
    out = eng.run()
    assert all(v.shape == (scfg.max_new_tokens,) for v in out.values())
    spans = eng.obs.spans()
    assert spans and eng.obs.dropped == 0
    m = measured_overlap(spans)
    assert 0.0 <= m["efficiency"] <= 1.0
    s = eng.metrics_snapshot()
    assert s["counters"]["serving.decode_steps"] > 0
    assert s["counters"].get("analysis.str002_live", 0) == 0
    assert s["histograms"]["latency.ttft_s"]["count"] == 2
