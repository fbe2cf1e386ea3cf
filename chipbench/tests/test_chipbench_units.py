"""The benchmark's yardstick without a model: the traffic generator, the
timeline arithmetic, the percentile, the peak table and the host-side
metric readers, on inputs whose answers are worked by hand."""

import dataclasses
import itertools
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import harness, peaks, timelines, traffic  # noqa: E402

BIG_SEED = 2 ** 31 + 12345


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    t0_ns: int
    t1_ns: int
    args: dict = dataclasses.field(default_factory=dict)


# -- traffic ------------------------------------------------------------------


def _window(mix, reqs, window_s):
    lead = mix["lead_s"]
    return [r for r in reqs if lead <= r.due_s < lead + window_s]


@pytest.mark.parametrize("mix", ["traffic/chat", "traffic/offline",
                                 "testdata/tiny_open"])
def test_mix_files_draw_on_the_chunk_grid(mix):
    m = traffic.load_mix(ROOT / "chipbench" / f"{mix}.json")
    window_s = 51.0 if m["loop"] == "open" and m["lead_s"] > 1 else 2.0
    n = traffic.period_size(m, window_s, clients=32)
    if m["loop"] == "open":
        reqs = traffic.generate(m, BIG_SEED, 3 * n, 1000, window_s)
    else:
        reqs = [r for s in traffic.streams(m, BIG_SEED, n, 1000)
                for r in itertools.islice(s, 3)]
    p, o = m["prompt"], m["output"]
    for r in reqs:
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert len(r.prompt) % p.get("round_up", 1) == 0
        assert 1 <= r.max_new <= o["max"]
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 1000
    assert sorted({len(r.prompt) for r in reqs}) == traffic.prompt_grid(m, n)
    assert traffic.longest(m, n) == (max(len(r.prompt) for r in reqs)
                                     + max(r.max_new for r in reqs))


def test_same_seed_same_inputs_other_seed_same_work():
    m = traffic.load_mix(ROOT / "chipbench" / "traffic" / "chat.json")
    w = 51.0
    a = traffic.generate(m, BIG_SEED, 200, 500, w)
    b = traffic.generate(m, BIG_SEED, 200, 500, w)
    c = traffic.generate(m, BIG_SEED + 1, 200, 500, w)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # Another seed: the window holds the same period in another order.
    wa, wc = _window(m, a, w), _window(m, c, w)
    assert len(wa) == len(wc) == traffic.period_size(m, w, 0)
    assert [len(r.prompt) for r in wa] != [len(r.prompt) for r in wc]
    key = lambda r: (len(r.prompt), r.max_new)  # noqa: E731
    assert sorted(map(key, wa)) == sorted(map(key, wc))
    gaps = lambda ws: sorted(np.round(np.diff(  # noqa: E731
        [r.due_s for r in ws] + [ws[0].due_s + w]), 9))
    assert gaps(wa) == gaps(wc)
    assert not np.array_equal(wa[0].prompt[:8], wc[0].prompt[:8])


def test_open_loop_gaps_average_the_rate():
    m = {"loop": "open", "rate_per_s": 4.0, "lead_s": 1.0,
         "prompt": {"dist": "uniform", "min": 8, "max": 8},
         "output": {"dist": "uniform", "min": 2, "max": 2}}
    reqs = traffic.generate(m, 7, 300, vocab=10, window_s=32.0)
    # The period is the window: 128 requests whose gaps add up to 32 s.
    assert traffic.period_size(m, 32.0, 0) == 128
    # The first is the earliest arrival at or after the loop's start.
    assert 0 <= reqs[0].due_s < reqs[128].due_s - reqs[127].due_s
    for k in range(len(reqs) - 128):
        assert reqs[k + 128].due_s - reqs[k].due_s == pytest.approx(32.0)
    assert len(_window(m, reqs, 32.0)) == 128


def test_closed_loop_clients_send_the_period_whatever_the_seed():
    m = traffic.load_mix(ROOT / "chipbench" / "traffic" / "offline.json")
    runs = []
    for seed in (BIG_SEED, BIG_SEED + 1):
        s = traffic.streams(m, seed, 8, 1000)
        reqs = [list(itertools.islice(x, 9)) for x in s]
        runs.append(reqs)
        for c in range(8):
            # A client sends the period in turn; its first request is cut.
            assert [len(r.prompt) for r in reqs[c][1:]] == (
                [len(r.prompt) for r in reqs[(c + 1) % 8][:8]])
            assert reqs[c][0].max_new <= reqs[c][8].max_new
    # Every seed sends the same sizes in the same order, other tokens.
    size = lambda r: (len(r.prompt), r.max_new)  # noqa: E731
    a, b = runs
    assert [[size(r) for r in x] for x in a] == [[size(r) for r in x]
                                                 for x in b]
    assert not np.array_equal(a[0][0].prompt, b[0][0].prompt)


def test_lognormal_quantiles_and_clipping():
    spec = {"dist": "lognormal", "median": 512, "sigma": 0.8,
            "round_up": 256, "min": 256, "max": 3072}
    ls = traffic.lengths(spec, 64)
    assert ls.min() == 256 and ls.max() == 3072
    # Just below the median, 512 * exp(-0.8 * 0.02) = 504 rounds up to 512;
    # just above it, 520 rounds up to 768.
    assert ls[31] == 512 and ls[32] == 768
    assert list(ls) == sorted(ls)


def test_unknown_distribution_is_an_error():
    with pytest.raises(ValueError):
        traffic.quantile({"dist": "pareto"}, 0.5)


# -- timelines ----------------------------------------------------------------


def _spans():
    # uid 1: admitted 100..200 (first token at 200), ticks end at 300 and
    # 500; uid 2: admitted 250..400, one spec burst of 3 tokens at 700.
    return [
        Span("admit", 100, 200, {"uid": 1, "prompt_len": 64}),
        Span("decode_tick", 210, 300, {"uids": [1], "toks": [1]}),
        Span("admit", 250, 400, {"uid": 2, "prompt_len": 128}),
        Span("decode_tick", 410, 500, {"uids": [1, 2], "toks": [1, 0]}),
        Span("spec_tick", 600, 700, {"uids": [2], "toks": [3]}),
        Span("evict", 710, 720, {"uid": 2}),
    ]


def test_reconstruct_timelines():
    tls = timelines.reconstruct(_spans())
    assert tls[1].admit_ns == 100 and tls[1].prompt_len == 64
    assert tls[1].token_ns == [200, 300, 500]
    assert tls[2].token_ns == [400, 700] and tls[2].token_counts == [1, 3]
    assert tls[2].first_ns == 400 and tls[2].evictions == 1


def test_gaps_and_tokens_in_a_window():
    tls = timelines.reconstruct(_spans())
    # uid 1's gaps end at 300 and 500; only 500 lies in [400, 800).
    assert timelines.gaps_ending_in(tls[1], 400, 800) == pytest.approx(
        [200e-9])
    # uid 2's burst of 3 splits its 300 ns gap into three of 100 ns.
    assert timelines.gaps_ending_in(tls[2], 0, 800) == pytest.approx(
        [100e-9] * 3)
    assert timelines.tokens_in(tls.values(), 0, 800) == 3 + 4
    assert timelines.tokens_in(tls.values(), 450, 800) == 1 + 3


def test_nearest_rank_percentile():
    vals = list(range(1, 101))
    assert timelines.percentile(vals, 90) == 90
    assert timelines.percentile(vals, 95) == 95
    assert timelines.percentile([5.0], 95) == 5.0
    assert timelines.percentile([], 95) is None


# -- peaks --------------------------------------------------------------------


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peaks_for("TPU v5 lite") == {
        "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


# -- host-side readers --------------------------------------------------------


def _run(**kw):
    spans = _spans()
    base = dict(cell=None, seed=1, seconds=1.0, setup_s=12.5, t0_ns=150,
                t1_ns=800, spans=spans,
                timelines=timelines.reconstruct(spans),
                due_ns={1: 90, 2: 150}, window_uids=[2], refused=0,
                counters0={"serving.preemptions": 2},
                counters1={"serving.preemptions": 5},
                compiles_in_window=(0, 0), submit_lag_s=[], peaks={})
    base.update(kw)
    return harness.Run(**base)


@pytest.mark.parametrize("metric,want", [
    ("ttft_p90_ms", (400 - 150) * 1e-6),
    ("queue_wait_p90_ms", (250 - 150) * 1e-6),
    ("itl_p95_ms", 200e-6),  # gaps in the window: 200 ns, 100 ns x 3
    ("tokens_per_s", (3 + 4) / 650e-9),  # every token at 200 ns or later
    ("preemptions", 3.0),
    ("setup_s", 12.5),
])
def test_host_side_readers(metric, want):
    got = harness.reader(ROOT, metric)(_run())
    assert got == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "decode_step_ms.open", "decode_step_ms.offline", "prefill_ms_per_ktok",
    "paged_attn_roofline", "mfu", "device_idle.open"])
def test_trace_readers_give_nothing_without_a_trace(metric):
    assert harness.reader(ROOT, metric)(_run()) is None


def test_a_request_never_served_counts_with_its_wait():
    run = _run(window_uids=[2, 3], due_ns={1: 90, 2: 150, 3: 300})
    # uid 3 never got a token: it counts as waiting until the last span
    # ended (720); the 90th percentile of two is the larger.
    assert harness.reader(ROOT, "ttft_p90_ms")(run) == pytest.approx(
        (720 - 300) * 1e-6)


def test_check_sample_keeps_the_longest_and_follows_the_seed():
    finished = {u: np.zeros(n, np.int32) for u, n in
                [(1, 5), (2, 40), (3, 7), (4, 9), (5, 11)]}
    prompts = {u: np.ones(3, np.int32) for u in finished}
    want = {"check": {"requests": 3, "tokens": 1000}}
    a = harness.check_sample(finished, prompts, want, BIG_SEED)
    b = harness.check_sample(finished, prompts, want, BIG_SEED)
    assert 2 in a and len(a) == 3 and a.keys() == b.keys()
    few = harness.check_sample(finished, prompts,
                               {"check": {"requests": 9, "tokens": 45}}, 3)
    assert 2 in few and sum(len(v[1]) for v in few.values()) >= 45


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        harness.load_cell(ROOT, "no-such-cell")


def test_benchmark_cells_load_by_name():
    for name in ("qwen3-4b.chat", "qwen3-4b.offline"):
        cell = harness.load_cell(ROOT, name)
        assert cell.chips == 1 and cell.end_to_end and cell.per_layer
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        for m in cell.per_layer + cell.end_to_end:
            assert callable(harness.reader(ROOT, m["name"]))
