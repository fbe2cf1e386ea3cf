"""Serving launcher: continuous-batching streamed engine over N requests.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b \
        --requests 4 --prompt-len 128 --new-tokens 16

The smoke preset of the arch is served by default; ``--full`` serves its
published-width config in its own dtypes (qwen3-4b: 36 layers, d_model
2560, bf16, about 8 GB of weights — one TPU v5e chip), with seeded random
weights built on the device.

Every servable arch — decoder-only transformers, SSMs (mamba2/jamba), and
encoder-decoder (whisper, per-request ``enc_inputs``) — goes through
``StreamedBatchEngine`` (request queue + slot pool, chunked prefill
interleaved with batched decode); prefix-LM archs (paligemma) and
``--sequential`` fall back to the single-request ``ServingEngine``.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

import repro.configs as configs
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as T
from repro.runtime.serving import (ServeConfig, ServingEngine,
                                   StreamedBatchEngine)


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b", choices=configs.list_archs())
    ap.add_argument("--full", action="store_true",
                    help="serve the published-width config (default: the "
                         "smoke preset)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--max-batch", type=int, default=4,
                    help="decode slots for continuous batching")
    ap.add_argument("--interleave", type=int, default=1,
                    help="decode steps per in-flight prefill chunk")
    ap.add_argument("--autotune", action="store_true",
                    help="measurement-driven tuning (repro.tuning): profile "
                         "the live backend, warm-start from the paper's "
                         "generic flow, coordinate-descend on measured "
                         "tokens/s, persist the plan to the tuning db")
    ap.add_argument("--tuning-db", default=None,
                    help="tuning-db JSON path (default $REPRO_TUNING_DB or "
                         "~/.cache/repro/tuning.json)")
    ap.add_argument("--tune-budget", type=int, default=12,
                    help="max measured candidate runs the tuner may spend")
    ap.add_argument("--retune", action="store_true",
                    help="ignore a cached TunedPlan and search afresh")
    ap.add_argument("--sequential", action="store_true",
                    help="force the one-request-at-a-time baseline")
    ap.add_argument("--paged", action="store_true",
                    help="page the batched KV cache (global pool + free "
                         "list + per-slot page tables)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="cache rows per KV page (paged mode)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="page-pool size; default = contiguous-parity")
    ap.add_argument("--kv-dtype", choices=("fp32", "int8", "fp8"),
                    default="fp32",
                    help="paged-pool storage dtype: int8/fp8 store quantized "
                         "pages with per-page per-kv-head scales (~4x the "
                         "concurrent requests per pool byte; greedy outputs "
                         "may diverge within the documented tolerance; "
                         "needs --paged)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="map common page-aligned prompt prefixes to the "
                         "same physical pages (copy-on-write; needs --paged)")
    ap.add_argument("--prefix-min-pages", type=int, default=1,
                    help="shortest prefix worth sharing, in pages")
    ap.add_argument("--paged-kernel", choices=("auto", "on", "off"),
                    default="auto",
                    help="decode through the Pallas pool kernel; auto = "
                         "backend default (on for TPU, off elsewhere)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="speculative multi-token decode: an n-gram/prompt-"
                         "lookup drafter proposes spec-k tokens, one "
                         "batched verify step scores them all, and slots "
                         "advance by the accepted prefix per tick")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per verify step")
    ap.add_argument("--state-snapshots", action="store_true",
                    help="mamba: reuse chunk-aligned SSM-state snapshots "
                         "across admissions (the SSM degradation of "
                         "prefix sharing)")
    ap.add_argument("--prefix-store", default=None,
                    help="path: persist the prefix registry across runs "
                         "(restored at engine construction, saved after "
                         "the run; needs --prefix-sharing)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record per-tick spans and write a Chrome/Perfetto "
                         "trace.json here after the run (open it at "
                         "ui.perfetto.dev); also prints the measured "
                         "overlap efficiency vs the R-gate prediction")
    ap.add_argument("--metrics", action="store_true",
                    help="print engine.metrics_snapshot() as JSON after "
                         "the run (counters, latency histograms, pool "
                         "stats)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write engine.metrics_snapshot() as JSON to this "
                         "file after the run (the snapshot obs.doctor "
                         "consumes next to --trace)")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="SLO target: submit -> first token, milliseconds "
                         "(queue wait included); scored per request into "
                         "the snapshot's derived.slo block")
    ap.add_argument("--slo-itl-ms", type=float, default=None,
                    help="SLO target: worst per-token inter-token latency, "
                         "milliseconds (an eviction stall lands here)")
    args = ap.parse_args()
    if args.prefix_sharing and not args.paged:
        ap.error("--prefix-sharing requires --paged")
    if args.kv_dtype != "fp32" and not args.paged:
        ap.error("--kv-dtype quantizes the paged pool; it requires --paged")

    cfg = configs.get_config(args.arch) if args.full else \
        configs.get_smoke_config(args.arch)
    # Jitted, so the weights are made on the device without host copies or
    # per-op f32 temporaries.
    params = jax.jit(T.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))
    max_seq = args.prompt_len + cfg.prefix_len + args.new_tokens
    if args.paged:  # pages must tile the cache
        max_seq = -(-max_seq // args.block_size) * args.block_size
    scfg = ServeConfig(
        max_seq=max_seq,
        prefill_chunk=args.prefill_chunk,
        max_new_tokens=args.new_tokens,
        temperature=args.temperature,
        max_batch=args.max_batch,
        decode_interleave=args.interleave,
        paged=args.paged,
        block_size=args.block_size,
        num_blocks=args.num_blocks,
        kv_dtype=args.kv_dtype,
        paged_kernel={"auto": None, "on": True, "off": False}[
            args.paged_kernel],
        prefix_sharing=args.prefix_sharing,
        prefix_min_pages=args.prefix_min_pages,
        spec_decode=args.spec_decode,
        spec_k=args.spec_k,
        state_snapshots=args.state_snapshots,
        prefix_store=args.prefix_store)

    b = args.requests
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (b, args.prompt_len), 0, cfg.vocab_size)
    if args.prefix_sharing:
        # shared-system-prompt workload: the first (page-aligned) half of
        # every prompt is the same SYNC prefix, the tails stay unique
        sys_len = max(args.block_size, (args.prompt_len // 2)
                      // args.block_size * args.block_size)
        sys_tok = jax.random.randint(
            jax.random.PRNGKey(4), (sys_len,), 0, cfg.vocab_size)
        tokens = tokens.at[:, :sys_len].set(sys_tok[None])

    enc_inputs = None
    if cfg.is_encoder_decoder:  # whisper: per-request encoded-audio prefix
        enc_inputs = 0.1 * jax.random.normal(
            jax.random.PRNGKey(2), (b, cfg.encoder_seq, cfg.d_model))

    batched = not (cfg.prefix_len or args.sequential)
    slo_flags = (args.slo_ttft_ms is not None or args.slo_itl_ms is not None)
    if (args.trace or args.metrics or args.metrics_out
            or slo_flags) and not batched:
        ap.error("--trace/--metrics/--metrics-out/--slo-* instrument "
                 "StreamedBatchEngine; this arch/flag combination falls "
                 "back to the sequential engine")
    if not batched:
        kw = {}
        if enc_inputs is not None:
            kw["enc_inputs"] = enc_inputs
        if cfg.prefix_len:
            kw["prefix_embeds"] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(3), (b, cfg.prefix_len, cfg.d_model))
        eng = ServingEngine(cfg, params, scfg)
        t0 = time.perf_counter()
        out = eng.generate(tokens, **kw)
        dt = time.perf_counter() - t0
        rows = out.tolist()
        total_new = out.shape[0] * out.shape[1]
        mode = "sequential-batch"
    else:
        plan = None
        if args.autotune:
            from repro import tuning
            desc = tuning.WorkloadDescriptor.from_prompts(
                [np.asarray(tokens[i]) for i in range(b)],
                max_new_tokens=args.new_tokens)
            db = tuning.TuningDB(args.tuning_db)
            fp = tuning.fingerprint(cfg, desc, scfg)
            plan = None if args.retune else db.get(fp)
            cached = plan is not None
            if plan is None:
                plan = tuning.search_tuned_plan(
                    cfg, params, scfg, desc,
                    budget=tuning.SearchBudget(max_trials=args.tune_budget),
                    log=print)
                db.put(plan)
            st = plan.measured_stage_times
            print(f"[serve] autotune ({'cached' if cached else 'searched'}, "
                  f"{plan.decision}/{plan.category}): "
                  f"chunk={plan.prefill_chunk} "
                  f"interleave={plan.decode_interleave} "
                  f"block={plan.block_size} slots={plan.max_batch} "
                  f"kernel={plan.paged_kernel} "
                  f"(chunk {st.h2d * 1e3:.2f}ms, decode {st.kex * 1e3:.2f}ms; "
                  f"{plan.tokens_per_s:.1f} tok/s measured vs "
                  f"{plan.baseline_tokens_per_s:.1f} analytic; db {db.path})")
        tracer = None
        if args.trace:
            from repro.obs import Tracer
            tracer = Tracer()
        slo = None
        if slo_flags:
            from repro.obs import SLOPolicy
            slo = SLOPolicy.from_ms(ttft_ms=args.slo_ttft_ms,
                                    itl_ms=args.slo_itl_ms)
        eng = StreamedBatchEngine(cfg, params, scfg, plan=plan,
                                  tracer=tracer, slo=slo)
        t0 = time.perf_counter()
        uids = [eng.submit(
            np.asarray(tokens[i]),
            enc_inputs=(None if enc_inputs is None
                        else np.asarray(enc_inputs[i])))
            for i in range(b)]
        outs = eng.run()
        saved = eng.save_prefixes()
        dt = time.perf_counter() - t0
        rows = [outs[u].tolist() for u in uids]
        total_new = sum(len(r) for r in rows)
        mode = (f"continuous-batching x{args.max_batch} slots, "
                f"{eng.decode_steps} batched decode steps")
        if args.paged:
            st = eng.kv.stats(active_slots=eng.peak_active)
            mode += (f", paged block={eng.kv.block_size} "
                     f"(peak {st.peak_in_use}/{st.capacity} pages, "
                     f"{st.page_bytes}B/page)")
            if args.kv_dtype != "fp32":
                mode += f", kv-dtype {eng.kv.kv_dtype}"
            if args.prefix_sharing:
                mode += (f", prefix-sharing {eng.prefix_hits} hits / "
                         f"{eng.prefix_pages_shared} pages mapped "
                         f"({eng.prefix_pages_shared * st.page_bytes}B of "
                         f"prefill copies avoided, "
                         f"{eng.kv.cow_forks} COW forks)")
            if args.prefix_store:
                mode += (f", prefix-store {eng.prefixes_restored} restored"
                         f" / {saved} saved")
        if args.state_snapshots:
            mode += (f", state-snapshots {eng.snapshot_hits} hits / "
                     f"{eng.snapshot_tokens_reused} prompt tokens skipped")
        if args.spec_decode:
            rate = eng.spec_accepted / max(1, eng.spec_proposed)
            decoded = total_new - eng.admissions  # first tokens are prefill's
            mode += (f", spec-decode k={eng.scfg.spec_k}: "
                     f"{eng.spec_accepted}/{eng.spec_proposed} drafts "
                     f"accepted ({rate:.0%}), "
                     f"{decoded / max(1, eng.decode_steps):.2f} "
                     f"tokens/step over {eng.spec_ticks} verify + "
                     f"{eng.decode_steps - eng.spec_ticks} plain ticks")

    print(f"[serve] {args.arch} ({mode}): {b} requests x {args.prompt_len} "
          f"prompt -> {total_new // b} new tokens each in {dt:.2f}s "
          f"({total_new / dt:.1f} tok/s incl. prefill+compile)")
    for i, row in enumerate(rows[: min(3, b)]):
        print(f"[serve] req{i}: {row[:12]}{'...' if len(row) > 12 else ''}")
    if batched and args.trace:
        from repro.obs import (overlap_report, reconstruct_timelines,
                               timeline_aggregates)
        eng.obs.to_chrome(args.trace)
        rep = overlap_report(eng.obs.spans(),
                             stage_times=eng.last_stage_times,
                             dropped=eng.obs.dropped)
        m = rep["measured"]
        line = (f"[serve] trace: {args.trace} "
                f"({len(eng.obs.spans())} spans, "
                f"{eng.obs.dropped} dropped) — overlap "
                f"{m['efficiency']:.0%} ({m['hidden_s'] * 1e3:.1f}ms of "
                f"{m['total_s'] * 1e3:.1f}ms transfer hidden)")
        if m["partial"]:
            # ring wrap lost the head of the timeline: the number above
            # is from a truncated window, never report it as the run's
            line += " [PARTIAL: ring wrapped, efficiency is truncated]"
        if "predicted" in rep:
            p = rep["predicted"]
            line += (f"; R-gate predicts {p['efficiency']:.0%} "
                     f"({p['decision']}, n={p['n_streams']})")
        print(line)
        agg = timeline_aggregates(reconstruct_timelines(
            eng.obs.spans(), dropped=eng.obs.dropped, warn=False))
        print(f"[serve] requests: {agg['requests']} timelines "
              f"({agg['finished']} finished, {agg['partial']} partial) — "
              f"ttft p50 {agg['ttft_p50_s'] * 1e3:.1f}ms, queue wait p50 "
              f"{agg['queue_wait_p50_s'] * 1e3:.1f}ms, itl p50 "
              f"{agg['itl_p50_s'] * 1e3:.2f}ms, "
              f"{agg['evictions']} evictions")
    if batched and (args.metrics or args.metrics_out):
        import json
        snap = eng.metrics_snapshot()
        if args.metrics_out:
            with open(args.metrics_out, "w") as f:
                json.dump(snap, f, indent=2, sort_keys=True)
            print(f"[serve] metrics: {args.metrics_out}")
        if args.metrics:
            print(json.dumps(snap, indent=2, sort_keys=True))


if __name__ == "__main__":
    main()
