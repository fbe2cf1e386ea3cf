"""Measurement-driven autotuning subsystem (``repro.tuning``): workload
classification, tuning-db round-trips and schema fallback, and the bounded
search's parity + improvement contracts."""

import dataclasses
import json

import jax
import numpy as np
import pytest

import repro.configs as C
from repro.core import dependency as dep
from repro.core import rmetric
from repro.models import transformer as T
from repro.runtime.serving import ServeConfig, ServingEngine, StreamedBatchEngine
from repro import tuning
from repro.tuning import db as tdb
from repro.tuning import workload as twl


@pytest.fixture(scope="module")
def served():
    cfg = C.get_smoke_config("qwen3-4b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _desc(**kw):
    base = dict(prompt_len_mean=64, prompt_len_max=80, max_new_tokens=8,
                n_requests=4)
    base.update(kw)
    return twl.WorkloadDescriptor(**base)


class TestWorkloadDescriptor:
    def test_validation(self):
        with pytest.raises(ValueError):
            _desc(prompt_len_mean=0)
        with pytest.raises(ValueError):
            _desc(prompt_len_max=32)  # < mean
        with pytest.raises(ValueError):
            _desc(shared_prefix_fraction=1.5)
        with pytest.raises(ValueError):
            _desc(arrival="weird")

    def test_bucket_coarsens(self):
        """Nearby workloads share a bucket; far ones don't."""
        a = _desc(prompt_len_mean=60, n_requests=3)
        b = _desc(prompt_len_mean=64, n_requests=4)
        assert a.bucket() == b.bucket()
        assert (_desc(prompt_len_mean=300, prompt_len_max=300).bucket()
                != a.bucket())
        assert _desc(max_new_tokens=256).bucket() != a.bucket()

    def test_from_prompts_measures_shared_prefix(self):
        shared = np.arange(32, dtype=np.int32)
        prompts = [np.concatenate([shared, np.full(16, 99 + i, np.int32)])
                   for i in range(3)]
        d = twl.WorkloadDescriptor.from_prompts(prompts, max_new_tokens=4)
        assert d.n_requests == 3
        assert d.prompt_len_mean == 48
        assert d.shared_prefix_len == 32

    def test_synth_prompts_round_trip(self):
        d = _desc(shared_prefix_fraction=0.5, n_requests=5)
        prompts = twl.synth_prompts(d, vocab_size=1000, seed=3)
        assert len(prompts) == 5
        assert min(len(p) for p in prompts) == d.prompt_len_mean
        assert max(len(p) for p in prompts) == d.prompt_len_max
        back = twl.WorkloadDescriptor.from_prompts(prompts, max_new_tokens=8)
        assert back.shared_prefix_len >= d.shared_prefix_len


class TestClassifier:
    """The descriptor -> paper-category mapping (§4.1 via core.dependency)."""

    def test_concurrent_unique_prompts_are_independent(self):
        assert twl.classify_workload(
            _desc(), prefill_chunk=16) is dep.Category.INDEPENDENT

    def test_single_request_chunked_is_true_dependent(self):
        """One request's chunked prefill is the RAW chain through the KV
        cache (NW-style wavefront): streamable."""
        d = _desc(n_requests=1)
        assert twl.classify_workload(
            d, prefill_chunk=16) is dep.Category.TRUE_DEPENDENT

    def test_single_request_one_shot_is_sync(self):
        d = _desc(n_requests=1, prompt_len_max=64)
        assert twl.classify_workload(
            d, prefill_chunk=64) is dep.Category.SYNC

    def test_decode_dominated_is_iterative(self):
        d = _desc(max_new_tokens=512)
        cat = twl.classify_workload(d, prefill_chunk=16)
        assert cat is dep.Category.ITERATIVE and not cat.streamable

    def test_moderate_shared_prefix_reduces_to_false_dependent(self):
        """SYNC by the paper's letter, but the engine's redundant-transfer
        / staged-once move keeps it streamable (the paper's own
        FALSE_DEPENDENT strategy)."""
        d = _desc(shared_prefix_fraction=0.5)
        assert twl.classify_workload(
            d, prefill_chunk=16) is dep.Category.FALSE_DEPENDENT

    def test_dominant_shared_prefix_stays_sync(self):
        """The lavaMD regime (§5): shared bytes ~= payload bytes, nothing
        left worth streaming."""
        d = _desc(shared_prefix_fraction=0.95)
        cat = twl.classify_workload(d, prefill_chunk=16)
        assert cat is dep.Category.SYNC and not cat.streamable

    def test_staged_prefix_unlocks_independent(self):
        d = _desc(shared_prefix_fraction=0.5)
        assert twl.classify_workload(
            d, prefill_chunk=16,
            prefix_staged=True) is dep.Category.INDEPENDENT

    def test_spec_decode_restreams_iterative(self):
        """Speculation restructures the per-token decode chain into verify
        chunks — a RAW chain like chunked prefill — so the decode-dominated
        workload leaves ITERATIVE and the tuner's search actually runs."""
        d = _desc(max_new_tokens=512)
        assert twl.classify_workload(
            d, prefill_chunk=16) is dep.Category.ITERATIVE
        cat = twl.classify_workload(
            d, prefill_chunk=16, spec_decode=True, spec_k=4)
        assert cat is dep.Category.TRUE_DEPENDENT and cat.streamable

    def test_spec_decode_leaves_other_categories_alone(self):
        """Speculation only re-graphs the decode-dominated shape; balanced
        workloads classify as before."""
        d = _desc()  # prefill-balanced: independent either way
        assert twl.classify_workload(
            d, prefill_chunk=16,
            spec_decode=True, spec_k=4) is dep.Category.INDEPENDENT


def _plan(fp="abc123", **kw):
    base = dict(
        fingerprint=fp, prefill_chunk=32, decode_interleave=2,
        block_size=16, num_blocks=None, max_batch=4, paged=True,
        paged_kernel=False, prefix_min_pages=1, tokens_per_s=120.0,
        admit_ms=3.5, baseline_tokens_per_s=100.0, baseline_admit_ms=5.0,
        stage_times=(0.004, 0.002, 0.0001), decision="stream",
        category="independent", max_seq=128, trials=6)
    base.update(kw)
    return tdb.TunedPlan(**base)


class TestTuningDB:
    def test_fingerprint_stability_and_sensitivity(self, served):
        cfg, _ = served
        d = _desc()
        kw = dict(backend="cpu", device_kind="cpu")
        assert (tdb.fingerprint(cfg, d, **kw)
                == tdb.fingerprint(cfg, _desc(prompt_len_mean=60), **kw)), \
            "same bucket -> same fingerprint"
        assert (tdb.fingerprint(cfg, d, **kw)
                != tdb.fingerprint(cfg, _desc(max_new_tokens=256), **kw))
        assert (tdb.fingerprint(cfg, d, **kw)
                != tdb.fingerprint(cfg, d, backend="tpu", device_kind="v5e"))
        other = C.get_smoke_config("phi4-mini-3.8b")
        assert tdb.fingerprint(cfg, d, **kw) != tdb.fingerprint(other, d, **kw)
        # the serving mode joins the key: paged and unpaged plans never mix
        paged = ServeConfig(max_seq=128, paged=True)
        flat = ServeConfig(max_seq=128)
        assert (tdb.fingerprint(cfg, d, paged, **kw)
                != tdb.fingerprint(cfg, d, flat, **kw))
        # ... nor do speculative and plain-decode plans
        spec = ServeConfig(max_seq=128, spec_decode=True)
        assert (tdb.fingerprint(cfg, d, spec, **kw)
                != tdb.fingerprint(cfg, d, flat, **kw))
        # ... nor quantized and fp32 pools (a chunk tuned against int8
        # page traffic means nothing for an fp32 pool)
        quant_sc = ServeConfig(max_seq=128, paged=True, kv_dtype="int8")
        assert (tdb.fingerprint(cfg, d, quant_sc, **kw)
                != tdb.fingerprint(cfg, d, paged, **kw))

    def test_round_trip(self, tmp_path):
        path = tmp_path / "tuning.json"
        db = tdb.TuningDB(path)
        plan = _plan()
        db.put(plan)
        again = tdb.TuningDB(path)  # fresh reader, same file
        got = again.get("abc123")
        assert got == plan
        assert again.get("unknown") is None

    def test_schema_mismatch_falls_back_to_retune(self, tmp_path):
        path = tmp_path / "tuning.json"
        tdb.TuningDB(path).put(_plan())
        raw = json.loads(path.read_text())
        raw["schema"] = tdb.SCHEMA_VERSION + 1
        path.write_text(json.dumps(raw))
        assert tdb.TuningDB(path).get("abc123") is None  # file-level
        raw["schema"] = tdb.SCHEMA_VERSION
        raw["entries"][0]["schema"] = tdb.SCHEMA_VERSION + 1
        path.write_text(json.dumps(raw))
        assert tdb.TuningDB(path).get("abc123") is None  # entry-level
        # a pre-kv_dtype (v3) store is rejected wholesale too: its plans
        # were measured without the quantized-pool dimension, and their
        # num_blocks was never byte-budget-equalized
        raw["schema"] = tdb.SCHEMA_VERSION - 1
        raw["entries"][0]["schema"] = tdb.SCHEMA_VERSION - 1
        for entry in raw["entries"]:
            entry.pop("kv_dtype", None)
        path.write_text(json.dumps(raw))
        assert tdb.TuningDB(path).get("abc123") is None

    def test_corrupt_file_falls_back_to_retune(self, tmp_path):
        path = tmp_path / "tuning.json"
        path.write_text("{not json")
        db = tdb.TuningDB(path)
        assert len(db) == 0
        db.put(_plan())  # and the store recovers by overwriting
        assert tdb.TuningDB(path).get("abc123") is not None

    def test_lru_bound(self, tmp_path):
        db = tdb.TuningDB(tmp_path / "t.json", max_entries=3)
        for i in range(4):
            db.put(_plan(fp=f"fp{i}"), save=False)
        assert len(db) == 3 and db.get("fp0") is None
        db.get("fp1")  # bump fp1 so fp2 is now the LRU entry
        db.put(_plan(fp="fp4"), save=False)
        assert db.get("fp2") is None and db.get("fp1") is not None

    def test_plan_validation(self):
        with pytest.raises(ValueError):
            _plan(prefill_chunk=0)
        with pytest.raises(ValueError):
            _plan(block_size=24)  # does not tile max_seq=128
        with pytest.raises(ValueError):
            _plan(spec_k=0)

    def test_spec_knobs_round_trip(self, tmp_path):
        plan = _plan(spec_decode=True, spec_k=2)
        db = tdb.TuningDB(tmp_path / "t.json")
        db.put(plan)
        got = tdb.TuningDB(tmp_path / "t.json").get("abc123")
        assert got.spec_decode and got.spec_k == 2
        base = ServeConfig(max_seq=128, paged=True, spec_decode=True)
        sc = got.apply(base)
        assert sc.spec_decode and sc.spec_k == 2

    def test_kv_dtype_round_trips_and_applies(self, tmp_path):
        plan = _plan(kv_dtype="int8", num_blocks=12)
        db = tdb.TuningDB(tmp_path / "t.json")
        db.put(plan)
        got = tdb.TuningDB(tmp_path / "t.json").get("abc123")
        assert got == plan and got.kv_dtype == "int8"
        sc = got.apply(ServeConfig(max_seq=128, paged=True))
        assert sc.kv_dtype == "int8"
        # same tuned max_seq -> the byte-budget-equalized pool travels too
        assert sc.num_blocks == 12
        with pytest.raises(ValueError):
            _plan(kv_dtype="int4")

    def test_apply_round_trips_into_serve_config(self):
        plan = _plan()
        base = ServeConfig(max_seq=128, prefill_chunk=16, max_new_tokens=4,
                           max_batch=2, paged=True, block_size=32)
        sc = plan.apply(base)
        assert (sc.prefill_chunk, sc.decode_interleave) == (32, 2)
        assert (sc.block_size, sc.max_batch) == (16, 4)
        assert sc.max_seq == 128 and sc.max_new_tokens == 4  # policy stays
        chunk_cap, page_cap = plan.jit_cache_caps()
        assert (sc.chunk_jit_cap, sc.page_jit_cap) == (chunk_cap, page_cap)
        # a block size that doesn't tile the base geometry is not applied
        # (40 % 16 != 0): the base's own geometry survives
        sc2 = plan.apply(dataclasses.replace(base, max_seq=40, block_size=8))
        assert sc2.block_size == 8 and sc2.max_seq == 40
        ServeConfig(**dataclasses.asdict(sc2))  # still a valid config
        # a pool size tuned against a different max_seq is not trusted
        # across it (it could break the must-finish-alone guarantee for
        # longer same-bucket requests): the base pool survives
        sc3 = _plan(num_blocks=8).apply(
            dataclasses.replace(base, max_seq=256, num_blocks=20))
        assert sc3.num_blocks == 20 and sc3.block_size == 16
        assert sc3.chunk_jit_cap >= 2 * (256 // 32)  # caps follow the base


class TestSearch:
    def test_budget_validation(self):
        with pytest.raises(ValueError):
            tuning.SearchBudget(max_trials=0)

    def test_profile_engine_measures_all_stages(self, served):
        cfg, params = served
        scfg = ServeConfig(max_seq=64, prefill_chunk=16, max_new_tokens=2,
                           max_batch=2, paged=True, block_size=16)
        eng = StreamedBatchEngine(cfg, params, scfg)
        prof = tuning.profile_engine(eng, 32, repeats=1)
        assert prof.chunk_s > 0 and prof.decode_s > 0
        assert prof.h2d_s > 0 and prof.d2h_s >= 0
        assert prof.scatter_s > 0 and prof.gather_s > 0  # paged probes ran
        st = prof.stage_times()
        assert st.h2d >= prof.chunk_s and st.kex == prof.decode_s
        # the probe borrowed a slot and gave it back
        assert eng.kv.pages_in_use == 0

    def test_search_parity_and_improvement(self, served):
        """The acceptance contract: a capped-budget tune returns a plan
        whose measured tokens/s >= the analytic warm start's, and whose
        engine produces greedy outputs bitwise identical to the untuned
        paged path."""
        cfg, params = served
        scfg = ServeConfig(max_seq=96, prefill_chunk=16, max_new_tokens=6,
                           max_batch=2, paged=True, block_size=16)
        desc = _desc(prompt_len_mean=32, prompt_len_max=48,
                     max_new_tokens=6, n_requests=3)
        plan = tuning.search_tuned_plan(
            cfg, params, scfg, desc,
            budget=tuning.SearchBudget(max_trials=4, sweeps=1))
        assert plan.tokens_per_s >= plan.baseline_tokens_per_s
        assert plan.trials <= 4
        assert plan.schema == tdb.SCHEMA_VERSION

        prompts = twl.synth_prompts(desc, vocab_size=cfg.vocab_size)
        ref_eng = StreamedBatchEngine(cfg, params, scfg)
        uids = [ref_eng.submit(p, max_new_tokens=6) for p in prompts]
        ref = ref_eng.run()
        tuned_eng = StreamedBatchEngine(cfg, params, scfg, plan=plan)
        tuids = [tuned_eng.submit(p, max_new_tokens=6) for p in prompts]
        got = tuned_eng.run()
        for u, tu in zip(uids, tuids):
            np.testing.assert_array_equal(got[tu], ref[u])
        # the tuned caps actually reached the compile caches
        assert (tuned_eng.single._chunk_jit_cap
                == tuned_eng.scfg.chunk_jit_cap)
        assert tuned_eng.kv._jit_cap == tuned_eng.scfg.page_jit_cap

    def test_spec_search_explores_spec_k_and_streams(self, served):
        """The acceptance contract for the new knob: with spec_decode on, a
        decode-dominated workload classifies streamable (no single-stream
        short-circuit) and the search explores spec_k — the returned plan
        carries the mode and a valid tuned draft length."""
        cfg, params = served
        scfg = ServeConfig(max_seq=96, prefill_chunk=16, max_new_tokens=24,
                           max_batch=2, paged=True, block_size=16,
                           spec_decode=True, spec_k=4)
        desc = _desc(prompt_len_mean=24, prompt_len_max=24,
                     max_new_tokens=24, n_requests=2)
        plan = tuning.search_tuned_plan(
            cfg, params, scfg, desc,
            budget=tuning.SearchBudget(max_trials=4, sweeps=1))
        assert plan.category == "true-dependent"  # not iterative any more
        assert plan.spec_decode and 1 <= plan.spec_k <= 16
        assert plan.tokens_per_s >= plan.baseline_tokens_per_s
        # spec_k sits in the sweep order right after the prefill chunk
        from repro.tuning.search import _DIMS
        assert "spec_k" in _DIMS

    def test_non_streamable_short_circuits(self, served):
        """A decode-dominated workload must come back single-stream: one-
        shot prefill, no interleave — without paying chunk candidates."""
        cfg, params = served
        scfg = ServeConfig(max_seq=128, prefill_chunk=16, max_new_tokens=64,
                           max_batch=2)
        desc = _desc(prompt_len_mean=24, prompt_len_max=24,
                     max_new_tokens=64, n_requests=2)
        plan = tuning.search_tuned_plan(
            cfg, params, scfg, desc,
            budget=tuning.SearchBudget(max_trials=2, sweeps=1))
        assert plan.category == "iterative"
        assert plan.decode_interleave == 1
        # the winner is the untuned base or the pinned one-shot start —
        # never a searched chunk candidate
        assert plan.prefill_chunk in (scfg.prefill_chunk,
                                      desc.prompt_len_max)

    def test_serve_launcher_autotune_persists_plan(self, served, tmp_path,
                                                   monkeypatch, capsys):
        """`python -m repro.launch.serve --autotune` end to end on CPU:
        produces and persists a TunedPlan, and the served outputs match
        the untuned engine's (greedy parity)."""
        import repro.launch.serve as serve_mod
        db_path = tmp_path / "tuning.json"
        argv = ["serve", "--arch", "qwen3-4b", "--requests", "2",
                "--prompt-len", "24", "--new-tokens", "4",
                "--prefill-chunk", "8", "--max-batch", "2", "--paged",
                "--autotune", "--tune-budget", "3",
                "--tuning-db", str(db_path)]
        # The launcher's persistent compile cache stays out of tests.
        monkeypatch.setattr(serve_mod, "use_compile_cache", lambda: None)
        monkeypatch.setattr("sys.argv", argv)
        serve_mod.main()
        out = capsys.readouterr().out
        assert "autotune (searched" in out
        assert db_path.exists()
        db = tdb.TuningDB(db_path)
        assert len(db) == 1
        # second invocation hits the cache instead of re-searching
        monkeypatch.setattr("sys.argv", argv)
        serve_mod.main()
        assert "autotune (cached" in capsys.readouterr().out


class TestEngineSatellites:
    """The knob-change housekeeping that rides along with the tuner."""

    def test_autotune_retains_plan_and_stage_times(self, served):
        cfg, params = served
        scfg = ServeConfig(max_seq=64, prefill_chunk=16, max_new_tokens=2,
                           max_batch=2)
        eng = StreamedBatchEngine(cfg, params, scfg)
        assert eng.last_plan is None and eng.last_stage_times is None
        plan = eng.autotune(32)
        assert eng.last_plan is plan  # not discarded after planning
        assert eng.last_stage_times == plan.stage_times
        assert plan.stage_times.h2d > 0 and plan.stage_times.kex > 0

    def test_chunk_change_clears_stranded_prefixes(self, served,
                                                   monkeypatch):
        """Registry entries aligned to the old chunk grid are dropped when
        autotune changes prefill_chunk (they could never match again and
        would only pin pages until pool pressure reclaimed them)."""
        cfg, params = served
        scfg = ServeConfig(max_seq=96, prefill_chunk=24, max_new_tokens=2,
                           max_batch=2, paged=True, block_size=8,
                           prefix_sharing=True)
        eng = StreamedBatchEngine(cfg, params, scfg)
        eng.submit(np.arange(24, dtype=np.int32))
        eng.run()
        # the prompt registered its 24-token (3-page, chunk-aligned) prefix
        assert len(eng.kv.registry) == 1
        assert eng.kv.registry.blocks_held == 3
        assert eng.kv.stats().registry_pages == 3
        # deterministic plan: stream-band stage times -> chunk 16, block 8
        # (geometry unchanged, so no pool rebuild masks the stranded path)
        monkeypatch.setattr(
            eng, "measure_stage_times",
            lambda n: rmetric.StageTimes(h2d=0.004, kex=0.002))
        plan = eng.autotune(64)
        assert plan.prefill_chunk == 16 and eng.scfg.block_size == 8
        assert len(eng.kv.registry) == 0, (
            "a 24-token entry can never match on the 16-token chunk grid")
        assert eng.kv.pages_in_use == 0  # its pages came home
