"""The trace-driven diagnoser (``repro.obs.doctor``): each known-bad
fixture trips exactly the rule built for it, a healthy engine run trips
nothing high-severity, and the CLI round-trips with the right exit
codes."""

import json

import jax
import numpy as np
import pytest

import repro.configs as C
from repro.models import transformer as T
from repro.obs import Span, Tracer
from repro.obs.doctor import (Finding, SEVERITIES, diagnose, main,
                              render, report_json)
from repro.runtime.serving import ServeConfig, StreamedBatchEngine

MS = 1_000_000  # ns


def _admit(uid, t0, t1, *, queue_wait_s=0.0, chunks=1, slot=0,
           prompt_len=8, max_new=4):
    return Span("prefill", "admit", t0, t1, dict(
        uid=uid, chunks=chunks, shared_len=0, prompt_len=prompt_len,
        slot=slot, queue_wait_s=queue_wait_s, max_new=max_new))


def _tick(t0, t1, uids=(), toks=()):
    return Span("decode", "decode_tick", t0, t1,
                dict(uids=list(uids), toks=list(toks),
                     slot_ids=list(range(len(uids)))))


def _rules(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# known-bad fixtures: one rule each


class TestFixtures:
    def test_doc001_overlap_gap(self):
        """Prefill in-flight time never covered by decode, while the
        traced stage times predict streaming should hide most of it:
        DOC001 and nothing else."""
        spans = [
            # 10 chunks' worth of admission with zero decode inside it
            _admit(1, 0, 1000 * MS, chunks=10, max_new=3),
            # decode happens strictly after: nothing is hidden
            _tick(1000 * MS, 1100 * MS, [1], [1]),
            _tick(1100 * MS, 1200 * MS, [1], [1]),
        ]
        findings = diagnose(spans)
        assert _rules(findings) == ["DOC001"]
        f = findings[0]
        assert f.severity in ("info", "medium")
        assert f.evidence["measured"] == pytest.approx(0.0)
        assert f.evidence["predicted"] >= 0.30
        assert "prefill_chunk" in f.knobs

    def test_doc002_queue_wait_domination(self):
        """TTFT ~90% queue wait across 4 finished requests: DOC002 at
        medium, and nothing else (chunks=0 keeps stage-time estimation,
        and with it DOC001, out of the picture)."""
        spans = []
        for uid in range(4):
            t0 = uid * 100 * MS
            spans.append(_admit(uid, t0, t0 + 10 * MS, chunks=0,
                                queue_wait_s=0.090, max_new=2))
            spans.append(_tick(t0 + 10 * MS, t0 + 14 * MS, [uid], [1]))
        findings = diagnose(spans)
        assert _rules(findings) == ["DOC002"]
        f = findings[0]
        assert f.severity == "medium"
        assert f.evidence["median_queue_fraction"] == pytest.approx(0.9)
        assert "max_batch" in f.knobs

    def test_doc002_few_requests_downgraded_to_info(self):
        """The same symptom over only 2 requests is a noisy median:
        reported, but as info (a 3-request CI smoke must not fail a
        medium bar on it)."""
        spans = []
        for uid in range(2):
            t0 = uid * 100 * MS
            spans.append(_admit(uid, t0, t0 + 10 * MS, chunks=0,
                                queue_wait_s=0.090, max_new=2))
            spans.append(_tick(t0 + 10 * MS, t0 + 14 * MS, [uid], [1]))
        (f,) = diagnose(spans)
        assert f.rule == "DOC002" and f.severity == "info"

    def test_doc003_spec_collapse_from_snapshot(self):
        snapshot = {"counters": {"serving.spec_proposed": 200,
                                 "serving.spec_accepted": 20}}
        findings = diagnose([], snapshot=snapshot)
        assert _rules(findings) == ["DOC003"]
        f = findings[0]
        assert f.severity == "medium"
        assert f.evidence["acceptance"] == pytest.approx(0.1)
        assert "spec_k" in f.knobs

    def test_doc003_spec_collapse_from_spans(self):
        """Without a metrics snapshot the rule falls back to the
        spec_draft/spec_rollback span args."""
        spans = []
        t = 0
        for _ in range(20):
            spans.append(Span("decode", "spec_draft", t, t + MS,
                              dict(proposed=4)))
            spans.append(Span("decode", "spec_rollback", t + MS, t + 2 * MS,
                              dict(accepted=0)))
            t += 3 * MS
        findings = diagnose(spans)
        assert _rules(findings) == ["DOC003"]
        assert findings[0].evidence["proposed"] == 80

    def test_doc003_quiet_below_sample_floor(self):
        snapshot = {"counters": {"serving.spec_proposed": 8,
                                 "serving.spec_accepted": 0}}
        assert diagnose([], snapshot=snapshot) == []

    def test_doc004_pool_thrash(self):
        """4 requests, each evicted and readmitted: a page pool so tight
        decode turned into re-staging — DOC004 at high."""
        spans = []
        for uid in range(4):
            t0 = uid * 20 * MS
            spans.append(_admit(uid, t0, t0 + 5 * MS, max_new=99))
            spans.append(Span("transfer", "evict", t0 + 6 * MS, t0 + 7 * MS,
                              dict(uid=uid, pages=4, cur=9, slot=0)))
            spans.append(Span("transfer", "readmit", t0 + 9 * MS,
                              t0 + 10 * MS,
                              dict(uid=uid, pages=4, shared_pages=0,
                                   slot=0)))
        findings = diagnose(spans)
        assert _rules(findings) == ["DOC004"]
        f = findings[0]
        assert f.severity == "high"
        assert f.evidence["per_request"] == pytest.approx(1.0)
        assert "num_blocks" in f.knobs

    def test_doc005_live_str002_marker(self):
        spans = [Span("transfer", "STR002", 5 * MS, 5 * MS,
                      dict(tick=3, d2h_bytes=4096, budget=128))]
        findings = diagnose(spans)
        assert _rules(findings) == ["DOC005"]
        assert findings[0].severity == "high"
        assert findings[0].evidence["trace_markers"] == 1

    def test_doc005_live_str002_counter(self):
        snapshot = {"counters": {"analysis.str002_live": 2}}
        findings = diagnose([], snapshot=snapshot)
        assert _rules(findings) == ["DOC005"]
        assert findings[0].evidence["counter"] == 2

    def test_doc006_ring_wrap(self):
        spans = [_admit(1, 0, 10 * MS, chunks=0, max_new=2),
                 _tick(10 * MS, 14 * MS, [1], [1])]
        findings = diagnose(spans, dropped=17)
        assert _rules(findings) == ["DOC006"]
        f = findings[0]
        assert f.severity == "info"
        assert f.evidence["dropped_spans"] == 17
        assert f.evidence["partial_timelines"] == 1

    def test_high_severity_sorts_first(self):
        """A thrashing trace that also wrapped its ring: DOC004 (high)
        must outrank DOC006 (info)."""
        spans = []
        for uid in range(4):
            t0 = uid * 20 * MS
            spans.append(_admit(uid, t0, t0 + 5 * MS, max_new=99))
            spans.append(Span("transfer", "evict", t0 + 6 * MS, t0 + 7 * MS,
                              dict(uid=uid, pages=4, cur=9, slot=0)))
        findings = diagnose(spans, dropped=3)
        assert _rules(findings) == ["DOC004", "DOC006"]
        assert [f.severity for f in findings] == ["high", "info"]


# ---------------------------------------------------------------------------
# healthy stack


@pytest.fixture(scope="module")
def healthy_trace(tmp_path_factory):
    """A real traced paged run plus its metrics snapshot, on disk the
    way serve.py --trace/--metrics-out leaves them."""
    cfg = C.get_smoke_config("qwen3-4b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    scfg = ServeConfig(max_seq=64, prefill_chunk=16, max_new_tokens=5,
                       max_batch=2, paged=True, block_size=16)
    eng = StreamedBatchEngine(cfg, params, scfg, tracer=Tracer())
    for p in [np.asarray(jax.random.randint(
            jax.random.PRNGKey(30 + i), (n,), 0, cfg.vocab_size))
            for i, n in enumerate([24, 16, 32])]:
        eng.submit(p)
    eng.run()
    d = tmp_path_factory.mktemp("doctor")
    trace = d / "trace.json"
    metrics = d / "metrics.json"
    eng.obs.to_chrome(str(trace))
    metrics.write_text(json.dumps(eng.metrics_snapshot()))
    return eng, str(trace), str(metrics)


class TestHealthyStack:
    def test_no_high_severity(self, healthy_trace):
        eng, _, _ = healthy_trace
        findings = diagnose(eng.obs.spans(),
                            snapshot=eng.metrics_snapshot())
        assert all(f.severity != "high" for f in findings), \
            [f.as_dict() for f in findings]

    def test_report_json_schema(self, healthy_trace):
        eng, _, _ = healthy_trace
        findings = diagnose(eng.obs.spans())
        doc = report_json(findings, spans=len(eng.obs.spans()),
                          requests=3, dropped=0)
        assert doc["schema"] == 1
        s = doc["summary"]
        assert s["requests"] == 3 and s["dropped_spans"] == 0
        assert s["findings"] == len(doc["findings"])
        assert sum(s["by_severity"].values()) == s["findings"]
        assert s["worst_severity"] in (None,) + SEVERITIES
        for f in doc["findings"]:
            assert set(f) == {"rule", "severity", "title", "detail",
                              "category", "knobs", "score", "evidence"}

    def test_render_mentions_every_finding(self):
        findings = [Finding(rule="DOCX", severity="high", title="t",
                            detail="d", category="c", knobs=["k"])]
        out = render(findings, spans=5, requests=2, dropped=0)
        assert "DOCX" in out and "[HIGH]" in out and "knobs: k" in out
        assert "healthy" in render([], spans=5, requests=2, dropped=0)


# ---------------------------------------------------------------------------
# CLI


class TestCLI:
    def test_healthy_trace_passes_high_bar(self, healthy_trace, capsys):
        _, trace, metrics = healthy_trace
        rc = main([trace, "--metrics", metrics, "--fail-on", "high"])
        assert rc == 0
        assert "obs.doctor:" in capsys.readouterr().out

    def test_json_output_well_formed(self, healthy_trace, capsys):
        _, trace, metrics = healthy_trace
        rc = main([trace, "--metrics", metrics, "--json"])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["schema"] == 1
        assert doc["summary"]["worst_severity"] != "high"

    def test_fail_on_trips_on_bad_trace(self, tmp_path, capsys):
        """A thrashing fixture written through the real Chrome exporter
        makes the CLI exit 1 under --fail-on high."""
        tr = Tracer()
        for uid in range(4):
            t0 = tr.begin("admit")
            tr.add("prefill", "admit", t0, uid=uid, chunks=1,
                   shared_len=0, prompt_len=8, slot=0, queue_wait_s=0.0,
                   max_new=99)
            tr.add("transfer", "evict", tr.begin("evict"), uid=uid, pages=4, cur=9,
                   slot=0)
        path = tmp_path / "bad.json"
        tr.to_chrome(str(path))
        assert main([str(path), "--fail-on", "high"]) == 1
        out = capsys.readouterr().out
        assert "DOC004" in out
        assert main([str(path), "--fail-on", "never"]) == 0
