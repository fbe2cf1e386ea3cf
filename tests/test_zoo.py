"""Config-zoo serving: every architecture through the one streamed engine.

The fast tier pins the ServableModel taxonomy (``arch_kind_of``), the
per-arch dependency-category mapping (``tuning.workload.classify_workload``
with ``arch=``), the arch-dependent ``ServeConfig`` flag validation, and
the multi-request streamed parity contract — including a forced
evict/readmit cycle — for the two non-transformer servable kinds (mamba,
whisper) plus the mamba state-snapshot degradation of prefix sharing.

The slow-marked sweep (``-m slow -k zoo``, the nightly tier) builds a
servable and runs one streamed admission end-to-end for EVERY config in
``repro.configs``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.core import dependency as dep
from repro.models import transformer as T
from repro.runtime.model_iface import arch_kind_of, build_servable
from repro.runtime.serving import (ServeConfig, ServingEngine,
                                   StreamedBatchEngine)
from repro.tuning.workload import WorkloadDescriptor, classify_workload

#: The serving taxonomy each zoo config must land in — a new config that
#: falls outside this table is a test failure, not a silent default.
EXPECTED_KIND = {
    "qwen3-4b": "transformer",
    "gemma2-27b": "transformer",
    "internlm2-20b": "transformer",
    "mixtral-8x7b": "transformer",
    "phi4-mini-3.8b": "transformer",
    "qwen2-moe-a2.7b": "transformer",
    "mamba2-2.7b": "mamba",
    "jamba-1.5-large-398b": "mamba",
    "granite-4.0-h-small": "mamba",
    "whisper-medium": "whisper",
    "paligemma-3b": "prefix_lm",
}


def _scfg(**kw):
    return ServeConfig(max_seq=128, prefill_chunk=16, max_new_tokens=6,
                       max_batch=2, **kw)


def _build(arch):
    cfg = C.get_smoke_config(arch)
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def mamba_served():
    return _build("mamba2-2.7b")


@pytest.fixture(scope="module")
def whisper_served():
    return _build("whisper-medium")


class TestTaxonomy:
    def test_zoo_covers_every_arch(self):
        assert set(EXPECTED_KIND) == set(C.list_archs())

    @pytest.mark.parametrize("arch", sorted(EXPECTED_KIND))
    def test_arch_kind(self, arch):
        assert arch_kind_of(C.get_smoke_config(arch)) == EXPECTED_KIND[arch]

    def test_build_servable_stamps_kind(self):
        cfg = C.get_smoke_config("qwen3-4b")
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        scfg = _scfg()
        sv = build_servable(cfg, params, scfg)
        assert sv.kind == "transformer" and scfg.arch_kind == "transformer"

    def test_prefix_lm_rejected_before_params(self):
        # raises before touching params: a stub dict is enough
        cfg = C.get_smoke_config("paligemma-3b")
        with pytest.raises(NotImplementedError, match="prefix-LM"):
            build_servable(cfg, {}, _scfg())
        with pytest.raises(NotImplementedError, match="prefix-LM"):
            StreamedBatchEngine(cfg, {}, _scfg())


class TestArchValidation:
    """ServeConfig.validate_arch: arch-dependent flags fail fast with
    actionable messages (via build_servable's stamp, params untouched)."""

    def test_mamba_prefix_sharing_rejected(self):
        cfg = C.get_smoke_config("mamba2-2.7b")
        scfg = _scfg(paged=True, block_size=16, prefix_sharing=True)
        with pytest.raises(NotImplementedError, match="state_snapshots"):
            StreamedBatchEngine(cfg, {}, scfg)

    def test_mamba_spec_decode_rejected(self):
        cfg = C.get_smoke_config("mamba2-2.7b")
        scfg = _scfg(spec_decode=True)
        with pytest.raises(NotImplementedError, match="irreversible"):
            StreamedBatchEngine(cfg, {}, scfg)

    def test_whisper_prefix_sharing_rejected(self):
        cfg = C.get_smoke_config("whisper-medium")
        scfg = _scfg(paged=True, block_size=16, prefix_sharing=True)
        with pytest.raises(NotImplementedError, match="not shareable"):
            StreamedBatchEngine(cfg, {}, scfg)

    def test_whisper_spec_decode_rejected(self):
        cfg = C.get_smoke_config("whisper-medium")
        with pytest.raises(NotImplementedError):
            StreamedBatchEngine(cfg, {}, _scfg(spec_decode=True))

    def test_snapshots_need_mamba(self):
        cfg = C.get_smoke_config("qwen3-4b")
        with pytest.raises(ValueError, match="state_snapshots"):
            StreamedBatchEngine(cfg, {}, _scfg(state_snapshots=True))

    def test_snapshots_rejected_for_hybrid(self):
        # jamba carries attention KV too: O(max_seq) per snapshot entry
        cfg = C.get_smoke_config("jamba-1.5-large-398b")
        with pytest.raises(NotImplementedError, match="hybrid"):
            build_servable(cfg, {}, _scfg(state_snapshots=True))

    def test_prefix_store_needs_sharing(self):
        with pytest.raises(ValueError, match="prefix_sharing"):
            _scfg(prefix_store="/tmp/x.npz")


class TestCategoryMapping:
    """classify_workload maps each arch onto the paper's categories."""

    def _desc(self, prompt, new, n=1, **kw):
        return WorkloadDescriptor(
            prompt_len_mean=prompt, prompt_len_max=prompt,
            max_new_tokens=new, n_requests=n, **kw)

    def test_mamba_chunked_prefill_true_dependent(self):
        # RAW chain over the O(1) recurrent state, same category as the
        # transformer's KV chain
        cat = classify_workload(
            self._desc(128, 4), prefill_chunk=16, arch="mamba")
        assert cat is dep.Category.TRUE_DEPENDENT

    def test_whisper_one_shot_sync(self):
        # encode -> one decode stage: the paper's staged (SYNC) transfer
        cat = classify_workload(
            self._desc(16, 4), prefill_chunk=32, arch="whisper")
        assert cat is dep.Category.SYNC

    def test_whisper_chunked_prefill_true_dependent(self):
        # after the encode head, the decoder chunk chain is the usual RAW
        # handoff — streamable
        cat = classify_workload(
            self._desc(128, 4), prefill_chunk=16, arch="whisper")
        assert cat is dep.Category.TRUE_DEPENDENT

    def test_whisper_decode_dominated_iterative(self):
        cat = classify_workload(
            self._desc(16, 256, n=4), prefill_chunk=16, arch="whisper")
        assert cat is dep.Category.ITERATIVE

    def test_arch_default_matches_transformer(self):
        # the default keeps every pre-existing call site's behavior
        for desc, chunk in [(self._desc(128, 4), 16),
                            (self._desc(16, 256, n=4), 16),
                            (self._desc(64, 8, n=4), 32)]:
            assert (classify_workload(desc, prefill_chunk=chunk)
                    is classify_workload(desc, prefill_chunk=chunk,
                                         arch="transformer"))

    def test_unknown_arch_rejected(self):
        with pytest.raises(ValueError, match="unknown arch"):
            classify_workload(self._desc(64, 4), prefill_chunk=16,
                              arch="rnn")


def _parity_with_evict(cfg, params, scfg, *, enc=False, seed=1):
    """Streamed multi-request run (with one forced evict/readmit cycle
    mid-decode) must match the sequential single-request reference."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (20, 33, 17)]
    encs = [rng.standard_normal(
        (cfg.encoder_seq, cfg.d_model)).astype(np.float32) if enc else None
        for _ in prompts]
    eng = StreamedBatchEngine(cfg, params, scfg)
    uids = [eng.submit(p) if e is None else eng.submit(p, enc_inputs=e)
            for p, e in zip(prompts, encs)]
    for _ in range(3):
        if eng.pending:
            eng.step()
    assert eng.active_slots, "expected in-flight slots to evict"
    ev = eng.evict(eng.active_slots[0].uid)
    eng.readmit(ev)
    out = eng.run()

    single = ServingEngine(cfg, params, scfg)
    for uid, p, e in zip(uids, prompts, encs):
        kw = {} if e is None else {"enc_inputs": jnp.asarray(e[None])}
        ref = np.asarray(single.generate(jnp.asarray(p[None]), **kw))[0]
        np.testing.assert_array_equal(out[uid], ref)
    return eng


class TestMambaServing:
    def test_streamed_parity_evict_readmit(self, mamba_served):
        cfg, params = mamba_served
        _parity_with_evict(cfg, params, _scfg())

    def test_streamed_parity_paged(self, mamba_served):
        # SSM state rides the pool's opaque per-slot leaves
        cfg, params = mamba_served
        _parity_with_evict(cfg, params, _scfg(paged=True, block_size=16))

    def test_snapshot_reuse(self, mamba_served):
        """Two prompts sharing a 2-chunk head: the second admission
        restores the stored state and streams only the tail — token parity
        with a full prefill (the chunk-grid argument)."""
        cfg, params = mamba_served
        scfg = _scfg(state_snapshots=True)
        rng = np.random.default_rng(7)
        head = rng.integers(0, cfg.vocab_size, size=32).astype(np.int32)
        prompts = [np.concatenate([head, rng.integers(
            0, cfg.vocab_size, size=n).astype(np.int32)]) for n in (9, 14)]
        single = ServingEngine(cfg, params, scfg)
        refs = [np.asarray(single.generate(jnp.asarray(p[None]))[0])
                for p in prompts]
        eng = StreamedBatchEngine(cfg, params, scfg)
        uids = [eng.submit(p) for p in prompts]
        out = eng.run()
        for uid, ref in zip(uids, refs):
            np.testing.assert_array_equal(out[uid], ref)
        assert eng.snapshot_hits >= 1
        assert eng.snapshot_tokens_reused >= 32


class TestWhisperServing:
    def test_streamed_parity_evict_readmit(self, whisper_served):
        # the encoded audio prefix (SYNC stage) travels through
        # evict/readmit as per-slot cross-attention K/V
        cfg, params = whisper_served
        _parity_with_evict(cfg, params, _scfg(), enc=True)

    def test_streamed_parity_paged(self, whisper_served):
        cfg, params = whisper_served
        _parity_with_evict(cfg, params,
                           _scfg(paged=True, block_size=16), enc=True)

    def test_submit_requires_enc_inputs(self, whisper_served):
        cfg, params = whisper_served
        eng = StreamedBatchEngine(cfg, params, _scfg())
        with pytest.raises(ValueError, match="enc_inputs"):
            eng.submit(np.arange(8, dtype=np.int32))

    def test_submit_rejects_bad_enc_shape(self, whisper_served):
        cfg, params = whisper_served
        eng = StreamedBatchEngine(cfg, params, _scfg())
        bad = np.zeros((cfg.encoder_seq + 1, cfg.d_model), np.float32)
        with pytest.raises(ValueError, match="encoder_seq"):
            eng.submit(np.arange(8, dtype=np.int32), enc_inputs=bad)

    def test_text_arch_rejects_enc_inputs(self):
        cfg = C.get_smoke_config("qwen3-4b")
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        eng = StreamedBatchEngine(cfg, params, _scfg())
        enc = np.zeros((4, cfg.d_model), np.float32)
        with pytest.raises(ValueError, match="enc_inputs"):
            eng.submit(np.arange(8, dtype=np.int32), enc_inputs=enc)


@pytest.mark.slow
@pytest.mark.parametrize("arch", sorted(EXPECTED_KIND))
def test_zoo_streamed_smoke(arch):
    """Every zoo config either serves one streamed admission end-to-end or
    is rejected with a clear NotImplementedError (nightly sweep)."""
    cfg = C.get_smoke_config(arch)
    scfg = _scfg()
    if EXPECTED_KIND[arch] == "prefix_lm":
        with pytest.raises(NotImplementedError, match="prefix-LM"):
            StreamedBatchEngine(cfg, {}, scfg)
        return
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = StreamedBatchEngine(cfg, params, scfg)
    rng = np.random.default_rng(0)
    kw = {}
    if EXPECTED_KIND[arch] == "whisper":
        kw["enc_inputs"] = rng.standard_normal(
            (cfg.encoder_seq, cfg.d_model)).astype(np.float32)
    uid = eng.submit(
        rng.integers(0, cfg.vocab_size, size=24).astype(np.int32), **kw)
    out = eng.run()
    assert out[uid].shape == (scfg.max_new_tokens,)
