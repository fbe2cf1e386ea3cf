"""One run of one cell: set up, drive the window, read it, check it.

The system under test is ``StreamedBatchEngine`` (``runtime/serving.py``):
paged pool in the model's bfloat16, the Pallas paged-attention kernel,
fused chunked prefill, greedy sampling on the device, no prefix sharing and
no speculative decode. The engine's own ``Tracer`` is on in every run; its
spans say when each token reached the host.

An open loop submits each request when it falls due and calls ``step()``
while work is pending; a request's time to first token runs from its due
time. A closed loop has one client per slot, each sending its next
request as soon as its last one has finished. The window is ``seconds``
long; what is due in it is measured. After it closes the
loop runs on (arrivals still falling due) until every request due in the
window has its first token, for ``drain_s`` at most; one that never gets
it has failed.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import time
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import devtrace, reference, timelines, traffic
from chipbench import weights as W
from chipbench.dims import Dims, dims_of, load_config
from chipbench.peaks import peaks_for


class CompileClock:
    """Counts XLA compiles and persistent-cache loads (``jax.monitoring``)."""

    def __init__(self) -> None:
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def count(self) -> tuple[int, int, float]:
        return self.compiles, self.cache_hits, self.compile_s


@dataclasses.dataclass
class Cell:
    name: str
    root: Path
    conf: dict[str, Any]
    dims: Dims
    mix: dict[str, Any]
    chips: int
    end_to_end: list[dict[str, Any]]
    per_layer: list[dict[str, Any]]

    @property
    def server(self) -> dict[str, Any]:
        return self.conf["server"]


def _for_cell(entries, cell: str) -> list[dict[str, Any]]:
    return [m for m in entries if cell in m.get("workloads", [cell])]


def load_cell(root: str | os.PathLike, workload: str) -> Cell:
    """Find a cell of ``<root>/BENCHMARK.json`` and its files by name."""
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    conf = load_config(root / entry["file"])
    mix = traffic.load_mix(root / "chipbench" / "traffic"
                           / f"{w['traffic']}.json")
    return Cell(name=workload, root=root, conf=conf, dims=dims_of(conf),
                mix=mix, chips=int(w["chips"]),
                end_to_end=_for_cell(bench["end_to_end"], workload),
                per_layer=_for_cell(bench["per_layer"], workload))


def reader(root: Path, metric: str):
    """``metrics/<metric>.py``, or for ``base.variant`` names
    ``metrics/<base>.py``; its ``read(run)`` gives the value or None."""
    d = root / "chipbench" / "metrics"
    path = d / f"{metric}.py"
    if not path.exists():
        path = d / f"{metric.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- the server ---------------------------------------------------------------


def model_config(d: Dims):
    """The server's ``ModelConfig`` for these sizes, in bfloat16. Refuses
    an architecture the reference does not compute (only dense GQA with
    SwiGLU, rotary positions and a tied head)."""
    from repro import configs
    from repro.models.transformer import LayerSpec

    base = configs.get_config(d.arch)
    cfg = dataclasses.replace(
        base, n_layers=d.n_layers, d_model=d.d_model, n_heads=d.n_heads,
        n_kv_heads=d.n_kv_heads, head_dim=d.head_dim, d_ff=d.d_ff,
        vocab_size=d.vocab, rope_theta=d.rope_theta, qk_norm=d.qk_norm,
        tie_embeddings=d.tied, param_dtype=jnp.bfloat16,
        compute_dtype=jnp.bfloat16)
    plain = (cfg.layer_unit == (LayerSpec(mixer="attn", ffn="dense"),)
             and cfg.ffn_kind == "swiglu" and cfg.use_rope
             and not cfg.sinusoidal_pos and not cfg.sliding_window
             and not cfg.attn_softcap and not cfg.final_softcap
             and not cfg.sandwich_norm and not cfg.embed_scale
             and cfg.query_scale is None and cfg.tie_embeddings
             and not cfg.prefix_len and not cfg.is_encoder_decoder)
    if not plain or d.rotary_fraction != 1.0 or d.norm_eps != 1e-6:
        raise ValueError(f"{d.arch}: the server and the reference differ "
                         "for this architecture")
    return cfg


def build_engine(cell: Cell, cfg, params, tracer):
    from repro.runtime.serving import ServeConfig, StreamedBatchEngine

    s = cell.server
    scfg = ServeConfig(
        max_seq=s["max_seq"], prefill_chunk=s["prefill_chunk"],
        max_new_tokens=int(cell.mix["output"]["max"]),
        max_batch=s["max_batch"], paged=True, block_size=s["block_size"],
        num_blocks=s["num_blocks"], paged_kernel=True, fused_prefill=True,
        temperature=0.0, chunk_jit_cap=s["chunk_jit_cap"],
        page_jit_cap=s["page_jit_cap"])
    eng = StreamedBatchEngine(cfg, params, scfg, tracer=tracer)
    pool_dt = {x.dtype for x in jax.tree.leaves(eng.kv.pools)}
    if pool_dt != {jnp.dtype(jnp.bfloat16)} or not eng.scfg.fused_prefill:
        raise RuntimeError(f"server not as configured: pool {pool_dt}, "
                           f"fused_prefill {eng.scfg.fused_prefill}")
    return eng


def warm_up(eng, cell: Cell, period: int) -> dict[str, int]:
    """Run every program the cell's traffic can reach: one prefill per
    prompt length of the mix's period of ``period`` requests (every (chunk
    length, offset) program, the first-token pick and the decode step), and
    the page gather and scatter of a preemption for every page count a
    request of the period can hold."""
    rng = np.random.default_rng(0)
    grid = traffic.prompt_grid(cell.mix, period)
    for n in grid:
        eng.submit(rng.integers(0, cell.dims.vocab, n, dtype=np.int32), 2)
    eng.run()
    kv = eng.kv
    lo = kv.pages_for(min(grid))  # a request preempted at its first tick
    hi = kv.pages_for(min(traffic.longest(cell.mix, period), kv.max_seq))
    if not kv.alloc(0, hi * kv.block_size):
        raise RuntimeError(f"pool of {kv.num_blocks} pages cannot hold one "
                           f"request of {hi} pages")
    for n in range(lo, hi + 1):
        caches = kv.gather(0, n * kv.block_size)
        kv.scatter(0, caches, n * kv.block_size)
    jax.block_until_ready(kv.pools)
    kv.release(0)
    return {"prompt_lengths": len(grid), "page_counts": hi - lo + 1}


# -- the window ---------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """What one run measured; metric readers take it."""

    cell: Cell
    seed: int
    seconds: float
    setup_s: float
    t0_ns: int
    t1_ns: int
    spans: list
    timelines: dict[int, timelines.Timeline]
    due_ns: dict[int, int]  # uid -> due time (open loop)
    window_uids: list[int]  # due (open) or admitted (closed) in the window
    refused: int
    counters0: dict[str, Any]
    counters1: dict[str, Any]
    compiles_in_window: tuple[int, int]
    submit_lag_s: list[float]
    peaks: dict[str, float]
    traces: list[devtrace.DeviceTrace] | None = None

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-9


class _Profiler:
    """Traces the device from the window's first instant until the loop
    has drained (stopping the profiler stalls the host for a while, so it
    waits until no request of the window is left to serve). An ``ANCHOR``
    annotation marks the window inside the trace. Only the host's
    annotations are recorded beside the device: no Python tracer, no HLO.
    """

    def __init__(self, directory: Path, log):
        self.dir = directory
        self.log = log
        self.anchor_ns = 0
        self._ann = None

    def open(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(devtrace.ANCHOR)
        self._ann.__enter__()
        self.anchor_ns = time.perf_counter_ns()

    def close(self) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def stop(self) -> None:
        self.close()
        t = time.perf_counter()
        jax.profiler.stop_trace()
        self.log(f"profiler stopped in {time.perf_counter() - t:.3f}s")

    def read(self) -> list[devtrace.DeviceTrace]:
        files = sorted(self.dir.rglob("*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        t = time.perf_counter()
        try:
            return devtrace.load(str(files[-1]), self.anchor_ns)
        finally:
            self.log(f"trace of {files[-1].stat().st_size} B read in "
                     f"{time.perf_counter() - t:.1f}s")
            shutil.rmtree(self.dir, ignore_errors=True)


class _Driver:
    """Submits requests to the engine and steps it; keeps what it sent."""

    def __init__(self, eng, clock: CompileClock, prof: _Profiler | None):
        self.eng = eng
        self.clock = clock
        self.prof = prof
        self.prompts: dict[int, np.ndarray] = {}
        self.due: dict[int, int] = {}
        self.window: list[int] = []
        self.lag: list[float] = []
        self.refused = 0
        self.t0 = self.t1 = 0
        self.counters0: dict[str, Any] = {}
        self.compiles0 = (0, 0, 0.0)

    def submit(self, r: traffic.Request, max_new: int | None = None):
        try:
            uid = self.eng.submit(r.prompt, max_new or r.max_new)
        except ValueError:
            return None
        self.prompts[uid] = r.prompt
        return uid

    def open_window(self, seconds: float, t0: int | None = None) -> None:
        self.t0 = time.perf_counter_ns() if t0 is None else t0
        self.t1 = self.t0 + int(seconds * 1e9)
        self.counters0 = dict(self.eng.metrics.snapshot()["counters"])
        self.compiles0 = self.clock.count()
        if self.prof is not None:
            self.prof.open()

    def close_window(self) -> None:
        if self.prof is not None:
            self.prof.close()

    def run_open(self, reqs, *, seconds, lead_s, drain_s) -> None:
        eng = self.eng
        base = time.perf_counter_ns()
        due = [base + int(r.due_s * 1e9) for r in reqs]
        t0 = base + int(lead_s * 1e9)
        t1 = t0 + int(seconds * 1e9)
        deadline = t1 + int(drain_s * 1e9)
        i = 0
        while True:
            now = time.perf_counter_ns()
            if not self.t0 and now >= t0:
                self.open_window(seconds, t0)
            if now >= t1:
                self.close_window()
            while i < len(reqs) and due[i] <= now:
                inside = t0 <= due[i] < t1
                uid = self.submit(reqs[i])
                if uid is None:
                    self.refused += inside
                else:
                    self.due[uid] = due[i]
                    self.lag.append((time.perf_counter_ns() - due[i]) * 1e-9)
                    if inside:
                        self.window.append(uid)
                i += 1
            if now >= t1:
                waiting = {r.uid for r in eng.queue}
                if now >= deadline or not any(u in waiting
                                              for u in self.window):
                    break
            if eng.pending:
                eng.step()
            elif i < len(reqs):
                time.sleep(max(0, min(due[i] - now, 1_000_000)) * 1e-9)
            else:
                break

    def run_closed(self, clients, *, seconds) -> None:
        """Each client's first request is admitted before the window; a
        client sends its next request once its last one has finished."""
        eng = self.eng
        owner: dict[int, int] = {}

        def send(c: int) -> None:
            while (uid := self.submit(next(clients[c]))) is None:
                self.refused += 1
            owner[uid] = c

        def resend() -> None:
            for uid in [u for u in eng.outputs if u in owner]:
                send(owner.pop(uid))

        for c in range(len(clients)):
            send(c)
        while eng.queue:
            eng.step()
            resend()
        self.open_window(seconds)
        while time.perf_counter_ns() < self.t1:
            eng.step()
            resend()
        self.close_window()


def run_cell(cell: Cell, seed: int, seconds: float, *, trace: bool,
             process_t0_ns: int, clock: CompileClock, log=print,
             peak_table: dict | None = None, control: bool = False,
             check: bool = True, keep: dict | None = None) -> dict:
    """Set up, drive one window, read it and check it; the result line.
    ``control`` also reads the float8 control's gaps, for setting the
    limit; benchmark runs leave it off. ``check=False`` skips the reference
    and adds ``sweep``: what a rate sweep reads of the window. ``keep``, a
    dict, is given the run's record under ``"run"``."""
    from repro.obs import MetricsRegistry, Tracer

    d, mix = cell.dims, cell.mix
    dev = jax.devices()[0]
    peaks = peaks_for(dev.device_kind, peak_table)
    cfg = model_config(d)
    params = W.make_program_params(d, cfg.padded_vocab, seed)
    tracer = Tracer(capacity=1 << 22, enabled=True)
    eng = build_engine(cell, cfg, params, tracer)
    period = traffic.period_size(mix, seconds, cell.server["max_batch"])
    warmed = warm_up(eng, cell, period)
    tracer.clear()
    eng.metrics = MetricsRegistry()
    eng.outputs.clear()
    log(f"warmed {warmed['prompt_lengths']} prompt lengths and "
        f"{warmed['page_counts']} page counts; pool {eng.kv.num_blocks} "
        f"pages of {eng.kv.page_bytes} B")

    if mix["loop"] == "open":
        n = math.ceil(period / seconds * (mix["lead_s"] + seconds
                                          + mix["drain_s"])) + 1
        reqs = traffic.generate(mix, seed, n, d.vocab, seconds)
    else:
        reqs = traffic.streams(mix, seed, period, d.vocab)
    prof = (_Profiler(cell.root / "chipbench" / ".trace", log) if trace
            else None)
    drv = _Driver(eng, clock, prof)
    if mix["loop"] == "open":
        drv.run_open(reqs, seconds=seconds, lead_s=mix["lead_s"],
                     drain_s=mix["drain_s"])
    else:
        drv.run_closed(reqs, seconds=seconds)
    if prof is not None:
        prof.stop()
    c1 = clock.count()
    in_window = (c1[0] - drv.compiles0[0], c1[1] - drv.compiles0[1])
    counters1 = dict(eng.metrics.snapshot()["counters"])
    mem_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    spans = tracer.spans()
    tls = timelines.reconstruct(spans)
    window = drv.window
    if mix["loop"] != "open":  # closed: the requests admitted in the window
        window = [u for u, tl in tls.items() if tl.first_ns is not None
                  and drv.t0 <= tl.first_ns < drv.t1]
    run = Run(cell=cell, seed=seed, seconds=seconds,
              setup_s=(drv.t0 - process_t0_ns) * 1e-9, t0_ns=drv.t0,
              t1_ns=drv.t1, spans=spans, timelines=tls, due_ns=drv.due,
              window_uids=window, refused=drv.refused,
              counters0=drv.counters0, counters1=counters1,
              compiles_in_window=in_window, submit_lag_s=drv.lag,
              peaks=peaks)
    if prof is not None:
        run.traces = prof.read()
    if keep is not None:
        keep["run"] = run
    n, hits, secs = drv.compiles0
    log(f"set-up: {n} XLA compiles ({secs:.1f}s), {hits} persistent-cache "
        "loads")
    log(f"compiles in the window: {in_window[0]} XLA compiles, "
        f"{in_window[1]} persistent-cache loads")
    if drv.lag:
        log(f"generator lag: p50 {timelines.percentile(drv.lag, 50):.6f}s, "
            f"max {max(drv.lag):.6f}s over {len(drv.lag)} requests")
    log("in the window: " + ", ".join(
        f"{n} {name} spans of {s * 1e3:.1f} ms in all"
        for name, (n, s) in _span_totals(spans, drv.t0, drv.t1).items()))
    ticks = sorted((s.t1_ns - s.t0_ns) * 1e-6 for s in spans
                   if s.name == "decode_tick" and drv.t0 <= s.t0_ns < drv.t1)
    if ticks:
        log(f"decode ticks in the window (ms): median "
            f"{timelines.percentile(ticks, 50):.1f}, longest "
            f"{', '.join(f'{t:.1f}' for t in ticks[-5:])}")
    gaps, end = [], drv.t0
    for s in sorted(spans, key=lambda s: s.t0_ns):
        if drv.t0 <= s.t0_ns < drv.t1:
            gaps.append((s.t0_ns - end) * 1e-6)
        end = max(end, s.t1_ns)
    log(f"longest host gaps between engine spans in the window (ms): "
        f"{', '.join(f'{g:.1f}' for g in sorted(gaps)[-3:])}")

    queued = len(eng.queue)
    active = {s.uid for s in eng.active_slots}
    finished = {u: np.asarray(t) for u, t in eng.outputs.items()
                if u not in active}
    sample = check_sample(finished, drv.prompts, mix, seed) if check else {}
    # Free the server before the reference runs on the chip.
    del eng, params, drv
    gc.collect()

    failed = run.refused + sum(1 for u in window
                               if u not in tls or tls[u].first_ns is None)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = reader(cell.root, m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    result = {"correct": False, "attempted": len(window) + run.refused,
              "failed": failed, "metrics": metrics, "device": device}
    if run.traces:
        tr = run.traces[: cell.chips]
        device["busy_s"] = sum(map(devtrace.busy_s, tr)) / len(tr)
        device["window_s"] = tr[0].window_s
        result["breakdown"] = {
            "device_ops": devtrace.top_ops(tr[0]),
            "idle_gaps": devtrace.idle_gaps(tr[0], spans)}
    if not check:
        result["sweep"] = {
            "due": len(window), "queue_at_close": queued,
            "admitted_by_close": sum(
                1 for u in window if u in tls and tls[u].first_ns is not None
                and tls[u].first_ns < run.t1_ns),
            "tokens_per_s": timelines.tokens_in(
                tls.values(), run.t0_ns, run.t1_ns) / run.window_s}
        return result
    t_ref = time.perf_counter()
    rows = -(-int(mix["output"]["max"]) // 128) * 128
    gaps = reference.served_gaps(d, seed, [sample[u] for u in sorted(sample)],
                                 rows=rows, control=control)
    worst = float(np.max(gaps["program"]))
    limit = float(cell.conf["limits"]["logit_gap"])
    log(f"reference: {len(sample)} requests, {gaps['program'].size} served "
        f"tokens, {time.perf_counter() - t_ref:.1f}s")
    result["correct"] = bool(worst <= limit)
    result["check"] = {"logit_gap": {"value": worst, "limit": limit}}
    if control:
        result["check"]["control_logit_gap"] = {
            "value": float(np.max(gaps["control"])), "limit": limit}
    return result


def _span_totals(spans, t0_ns: int, t1_ns: int
                 ) -> dict[str, tuple[int, float]]:
    """Count and seconds of the engine's spans that start in the window,
    by name (what the host did; printed on stderr, not a metric)."""
    out: dict[str, tuple[int, float]] = {}
    for s in spans:
        if t0_ns <= s.t0_ns < t1_ns:
            n, secs = out.get(s.name, (0, 0.0))
            out[s.name] = (n + 1, secs + (s.t1_ns - s.t0_ns) * 1e-9)
    return dict(sorted(out.items()))


def check_sample(finished: dict[int, np.ndarray],
                 prompts: dict[int, np.ndarray], mix: dict[str, Any],
                 seed: int) -> dict[int, tuple[np.ndarray, np.ndarray]]:
    """The finished requests the reference checks: the one with the most
    served tokens, then others drawn from the seed until the mix's
    ``check`` count of requests or of served tokens is reached."""
    want = mix["check"]
    uids = sorted(finished)
    if not uids:
        raise RuntimeError("no request finished: nothing to check")
    longest = max(uids, key=lambda u: (len(finished[u]), -u))
    rest = [u for u in uids if u != longest]
    order = np.random.default_rng([seed, 0xc4ec]).permutation(len(rest))
    pick = [longest]
    served = len(finished[longest])
    for j in order:
        if len(pick) >= want["requests"] or served >= want["tokens"]:
            break
        pick.append(rest[j])
        served += len(finished[rest[j]])
    return {u: (prompts[u], finished[u]) for u in pick}
