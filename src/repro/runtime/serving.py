"""Serving engine: chunked (streamed) prefill + continuous-batching decode.

The paper's streaming flow applied to inference:

  * **Chunked prefill** — the prompt is split into chunks (tasks) processed
    left-to-right with a RAW KV-cache handoff (True-dependent streaming,
    like NW): chunk t+1's H2D/KV-DMA overlaps chunk t's compute on TPU, and
    peak activation memory drops from O(S) to O(chunk).
  * **Prefix SYNC** — for PaliGemma-style prefix-LM requests the image
    prefix is shared by every decode task: a non-streamable SYNC transfer
    (paper §4.1) that must complete before decode; the engine stages it
    once.
  * **Decode** — one step per token over the batch; requests are
    Independent tasks (paper §4.1) admitted into a fixed pool of slots.

Continuous-batching design (``StreamedBatchEngine``):

  * **Slots** — the decode batch has ``max_batch`` fixed slots sharing one
    batched KV cache of shape (layers, max_batch, max_seq, ...).  Each slot
    carries its own absolute cache position (``cur``), so rope, the cache
    write offset and the attention visibility mask are per row
    (``decode_step`` with a (B,) ``cur_len`` vector).  Inactive slots ride
    along as padding rows; their cache region is overwritten wholesale at
    the next admission.
  * **Admission / interleave** — a new request is prefilled chunk-by-chunk
    at batch 1 into a private cache; between dispatching chunk t+1 and
    consuming its result the engine runs ``decode_interleave`` batched
    decode steps for the active slots — the paper's pipeline with prefill
    chunks as the ingest (H2D-like) stage and batched decode as KEX.  The
    finished cache is then scattered into the slot's rows of the global
    cache.
  * **Eviction / readmission** — a slot's cache rows and positions can be
    pulled out (``evict``) and later written back into any free slot
    (``readmit``); positions travel with the request, so decode resumes
    exactly where it stopped (preemption / priority scheduling hook).
  * **Policy** — ``plan_decode_policy`` feeds measured (prefill-chunk,
    decode-step) ``StageTimes`` through the paper's generic flow (§6,
    the primitives behind ``streams.plan_streaming``): the R gate decides
    whether interleaving is worthwhile and ``rmetric.optimal_streams``
    sizes the prefill chunk count; the interleave ratio is the measured
    chunk/decode time ratio.

Paged KV cache (``ServeConfig.paged=True``, see ``repro.runtime.kv_cache``):

  * **Pages as Independent transfer tasks (§4.1)** — each slot's cache is a
    set of fixed-size pages drawn lazily from a global pool as ``cur``
    advances, so allocated HBM per request tracks its actual length instead
    of ``max_seq``; the freed headroom admits more concurrent Independent
    tasks (the same footprint-cutting move the paper uses to overlap
    transfers of different tasks).  The per-slot **page table is the RAW
    handoff** between decode steps — the True-dependence carrier that the
    chunked-prefill KV cache is between prefill chunks (§4.2).
  * **Admission backpressure / preemption** — a prompt whose pages don't fit
    waits in the queue; if the free list runs dry mid-decode, the youngest
    slot is preempted (pages gathered out, exactly like ``evict``) and
    readmitted when pages free up.  Greedy outputs stay token-identical to
    the contiguous path, which remains the ``paged=False`` default.
  * **Prefix sharing (``ServeConfig.prefix_sharing``)** — a common prompt
    prefix (shared system prompt) is the paging analog of the paper's SYNC
    transfer: data every task needs, staged once before streaming begins.
    Admission looks up the longest registered page-aligned prefix of the
    prompt, maps those physical blocks into the slot's table at refcount+1
    and chunk-prefills only the uncovered tail; whole pages free on
    refcount-zero and fork on write (copy-on-write), so greedy outputs stay
    token-identical to the unshared paged path while HBM footprint and
    admission prefill compute drop with every sharer.
  * **Block size as a policy knob** — ``plan_decode_policy`` sizes
    ``block_size`` from the same measured stage times that pick chunk and
    interleave (task granularity is the dominant knob in ML-guided tuning
    of streamed codes — Zhang et al., 1802.02760 / 2003.04294).
  * **Fused sampling** — the jitted decode step samples on device (argmax /
    per-slot-key categorical), so a tick transfers one int32 per slot
    instead of a (B, vocab) logits round-trip.

Speculative multi-token decode (``ServeConfig.spec_decode``, see
``repro.runtime.spec``):

  * **The ITERATIVE category, streamed** — plain decode is the paper's
    non-streamable ITERATIVE pattern (one kernel re-run per token on
    resident KV, a per-token RAW chain).  A drafter proposes ``spec_k``
    tokens per slot (model-free n-gram/prompt-lookup by default; any
    ``Drafter`` plugs in), one jitted verify step scores all ``k + 1``
    positions (``decode_step_multi[_paged]``: per-slot variable-length
    query blocks, causal masks inside the block), and each slot's ``cur``
    advances by its accepted prefix plus a bonus token — a *variable*
    number of tokens per tick.  The per-token chain becomes a chunked
    stream of verify tasks, the paper's "restructure the dependence, then
    stream" move, with ``spec_k`` as the new granularity knob the tuner
    searches.
  * **Rollback without corruption** — draft positions fault their pages up
    front (best-effort: a slot never preempts a neighbor to speculate);
    ``ensure_write`` COW-forks any shared target before the multi-token
    scatter, padding tails route to the trash block, and after acceptance
    ``kv.truncate`` returns the pages of rejected positions to the free
    list at refcount zero — shared/COW prefix pages are never corrupted
    and the pool invariant (``owned == pages_for(cur)``) is restored every
    tick.
  * **Parity** — greedy outputs are token-identical to the non-speculative
    path: an accepted draft equals the target argmax at its position by
    construction, so the emitted chain is exactly the plain greedy chain;
    temperature mode uses rejection sampling, which preserves the target
    distribution exactly (``repro.runtime.spec.verify``).
"""

from __future__ import annotations

import collections
import dataclasses
import time
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.budget import (budget_of, host_fetch, tick_path,
                                   transfer_budget)
from repro.core import rmetric
from repro.obs import MetricsRegistry, Tracer
from repro.kernels import quant
from repro.models import transformer as T
from repro.models.transformer import ModelConfig
from repro.runtime.kv_cache import PagedKVCache, _lru_jit


@dataclasses.dataclass
class ServeConfig:
    max_seq: int = 1024
    prefill_chunk: int = 256  # task size for streamed prefill
    max_new_tokens: int = 32
    temperature: float = 0.0  # 0 = greedy
    # continuous batching
    max_batch: int = 4  # decode slots
    decode_interleave: int = 1  # decode steps run per in-flight prefill chunk
    # paged KV cache
    paged: bool = False  # page the batched KV cache (kv_cache.PagedKVCache)
    block_size: int = 16  # cache rows per page
    num_blocks: int | None = None  # pool size; None = contiguous-parity + trash
    paged_kernel: bool | None = None  # decode via the Pallas pool kernel;
    # None = backend default (on for TPU, off elsewhere — the kernel's
    # scalar-prefetched page gather only pays off where Mosaic pipelines it)
    kv_dtype: str = "fp32"  # pool storage: "fp32" | "int8" | "fp8" —
    # quantized pools store narrow codes plus per-page, per-kv-head f32
    # scales (kernels/quant); ~2x effective page capacity at a bounded
    # greedy-token divergence (parity becomes tolerance-based, not bitwise)
    fused_prefill: bool | None = None  # write prefill K/V projections
    # straight into pool blocks through the page table (no contiguous slab
    # + second jitted scatter), and a recurrent slot's state straight into
    # its pool rows; None = on for paged transformer and mamba/hybrid archs
    # (not with state_snapshots), off elsewhere — resolved by validate_arch
    # once arch_kind is stamped
    prefix_sharing: bool = False  # map common prompt prefixes COW (SYNC once)
    prefix_min_pages: int = 1  # shortest prefix worth sharing, in pages
    # speculative multi-token decode (repro.runtime.spec): a drafter
    # proposes spec_k tokens, one batched verify step scores all k+1
    # positions, and cur advances by the accepted prefix + 1 per tick
    spec_decode: bool = False  # speculate/verify instead of 1 token/tick
    spec_k: int = 4  # draft tokens proposed per verify step
    spec_ngram: int = 3  # longest n-gram the default prompt-lookup matches
    # compile-cache bounds; None = module defaults, a TunedPlan sizes them
    # to its geometry (distinct pos0 offsets / admission page counts)
    chunk_jit_cap: int | None = None  # per-(len, first, pos0) prefill fns
    page_jit_cap: int | None = None  # per-n_pages scatter/gather/load fns
    # model-agnostic serving (runtime.model_iface): build_servable stamps
    # arch_kind from the model config and re-validates; setting it up
    # front validates arch-dependent flags before a model is in hand
    arch_kind: str | None = None  # "transformer" | "mamba" | "whisper"
    state_snapshots: bool = False  # mamba: reuse chunk-aligned SSM-state
    # snapshots across admissions (the SSM degradation of prefix sharing)
    prefix_store: str | None = None  # path: persist the prefix registry
    # across engine rebuilds (restored at construction, saved via
    # engine.save_prefixes; stale stores are ignored wholesale)

    def __post_init__(self) -> None:
        if self.paged_kernel is None:
            # Resolved at construction so every consumer (engine, tuner,
            # fingerprints) sees one concrete value per process.
            self.paged_kernel = jax.default_backend() == "tpu"
        if self.max_seq < 1:
            raise ValueError(f"max_seq must be >= 1, got {self.max_seq}")
        if self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {self.prefill_chunk}")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {self.max_new_tokens}")
        if self.decode_interleave < 1:
            raise ValueError(
                f"decode_interleave must be >= 1, got {self.decode_interleave}")
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}")
        if self.block_size < 1:
            raise ValueError(
                f"block_size must be >= 1, got {self.block_size}")
        if self.prefix_min_pages < 1:
            raise ValueError(
                f"prefix_min_pages must be >= 1, got {self.prefix_min_pages}")
        if self.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {self.spec_k}")
        if self.spec_ngram < 1:
            raise ValueError(
                f"spec_ngram must be >= 1, got {self.spec_ngram}")
        for cap in ("chunk_jit_cap", "page_jit_cap"):
            if getattr(self, cap) is not None and getattr(self, cap) < 1:
                raise ValueError(
                    f"{cap} must be >= 1 when set, got {getattr(self, cap)}")
        quant.validate_kv_dtype(self.kv_dtype)
        if quant.is_quantized(self.kv_dtype) and not self.paged:
            raise ValueError(
                "kv_dtype quantizes the paged KV pool; it requires "
                "paged=True (the contiguous cache stays full precision)")
        if self.fused_prefill and not self.paged:
            raise ValueError(
                "fused_prefill writes prefill K/V through the page table; "
                "it requires paged=True")
        if self.prefix_sharing and not self.paged:
            raise ValueError(
                "prefix_sharing shares physical KV pages; it requires "
                "paged=True")
        if self.paged:
            if self.max_seq % self.block_size != 0:
                raise ValueError(
                    f"max_seq {self.max_seq} must be a multiple of "
                    f"block_size {self.block_size} (pages tile the cache)")
            if self.num_blocks is not None and self.num_blocks < 2:
                raise ValueError(
                    f"num_blocks must be >= 2 (block 0 is the trash page), "
                    f"got {self.num_blocks}")
        if self.prefix_store is not None and not self.prefix_sharing:
            raise ValueError(
                "prefix_store persists the prefix registry; it requires "
                "prefix_sharing=True")
        self.validate_arch()

    def validate_arch(self) -> None:
        """Arch-dependent flag validation: actionable errors at config
        time, not a crash deep in the tick loop.  No-op until ``arch_kind``
        is stamped — ``model_iface.build_servable`` re-runs it with the
        model in hand, so a ServeConfig built before the model was known
        still fails fast at engine construction."""
        kind = self.arch_kind
        if kind is None:
            return
        if kind not in ("transformer", "mamba", "whisper"):
            raise ValueError(
                f"unknown arch_kind {kind!r}; expected "
                "transformer | mamba | whisper")
        if kind == "mamba":
            # Served: paged pools with fused prefill (the slot's SSM and
            # conv rows written in place). Refused: prefix sharing,
            # speculative decode and quantized pools.
            if self.prefix_sharing:
                raise NotImplementedError(
                    "prefix sharing maps attention KV pages; mamba/hybrid "
                    "archs carry per-slot SSM state with no page-granular "
                    "snapshot — state_snapshots=True gives the "
                    "chunk-aligned state-reuse degradation instead")
            if self.spec_decode:
                raise NotImplementedError(
                    "speculative decode rolls rejected positions back by "
                    "masking KV writes; mamba/hybrid archs advance "
                    "irreversible per-slot SSM state")
            if self.fused_prefill and self.state_snapshots:
                raise NotImplementedError(
                    "state_snapshots restore a b=1 prefill cache; fused "
                    "prefill keeps the slot's state in the pool — use one "
                    "or the other")
        if kind == "whisper":
            if self.prefix_sharing:
                raise NotImplementedError(
                    "prefix sharing keys pages by prompt tokens alone, but "
                    "whisper's self-attention KV depends on each request's "
                    "encoder output — identical text prefixes are not "
                    "shareable across requests")
            if self.spec_decode:
                raise NotImplementedError(
                    "speculative decode needs the multi-token verify step, "
                    "which has no cross-attention path; serve "
                    "encoder-decoder configs with spec_decode=False")
        if kind != "transformer":
            if quant.is_quantized(self.kv_dtype):
                raise NotImplementedError(
                    "quantized KV pages cover attention K/V pool blocks; "
                    f"arch_kind={kind!r} carries cache state (SSM rows / "
                    "cross-attention slabs) with no per-page scale — serve "
                    "it with kv_dtype='fp32'")
        if kind == "whisper" and self.fused_prefill:
            raise NotImplementedError(
                "fused_prefill routes prefill K/V through the decoder page "
                "table; whisper prefills through its cross-attention "
                "caches — leave fused_prefill unset")
        if self.fused_prefill is None:
            # Resolved here (not __post_init__) because the default depends
            # on the architecture: the fused path exists for the decoder
            # prefill chain over a paged pool, attention K/V and recurrent
            # state alike (not beside state snapshots).
            self.fused_prefill = bool(self.paged) and (
                kind == "transformer"
                or (kind == "mamba" and not self.state_snapshots))
        if self.state_snapshots and kind != "mamba":
            raise ValueError(
                "state_snapshots reuse recurrent SSM state across "
                f"admissions; arch_kind={kind!r} carries none (mamba only)")


# Chunk fns specialize per (len, first, pos0); shared-prefix tails admit at
# arbitrary page-aligned offsets, so the compile cache is a bounded LRU
# instead of growing one entry per distinct offset over a server's lifetime.
_CHUNK_JIT_CAP = 32


def slot_key(uid, step):
    """Per-request sampling key: folded from (uid, emitted-count) so a
    slot's draws depend only on its own stream — never on batch
    composition or on how tokens were grouped into ticks.  The one key
    recipe every sampler shares (host-side, fused decode, speculative
    verify); jit/vmap-traceable."""
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(0), uid), step)


def _slot_rows(pools: Any, slot: jax.Array, *, fresh: bool) -> Any:
    """The pools with each per-slot leaf cut to slot ``slot``'s (b=1) row,
    zeros when ``fresh``; paged leaves pass through whole."""
    def row(key, leaf):
        if key in T.PAGED_LEAVES:
            return leaf
        if fresh:
            return jnp.zeros(leaf.shape[:1] + (1,) + leaf.shape[2:],
                             leaf.dtype)
        return jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=1)

    return {"blocks": {name: {k: row(k, v) for k, v in c.items()}
                       for name, c in pools["blocks"].items()}}


def _put_slot_rows(pools: Any, rows: Any, slot: jax.Array) -> Any:
    """Inverse of :func:`_slot_rows`: each per-slot row written back into
    the pools at ``slot``; paged leaves are taken from ``rows`` whole."""
    def put(key, leaf, new):
        if key in T.PAGED_LEAVES:
            return new
        return jax.lax.dynamic_update_slice_in_dim(
            leaf, new.astype(leaf.dtype), slot, axis=1)

    return {"blocks": {
        name: {k: put(k, v, rows["blocks"][name][k]) for k, v in c.items()}
        for name, c in pools["blocks"].items()}}


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params: Any, scfg: ServeConfig):
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        self._sample_jit: dict[float, Any] = {}
        self._chunk_jit: collections.OrderedDict = collections.OrderedDict()
        self._chunk_jit_cap = scfg.chunk_jit_cap or _CHUNK_JIT_CAP

    def _decode_sample_fn(self, temperature: float):
        """Jitted decode step with on-device sampling fused in (one compile
        per temperature; greedy/temp is a static branch)."""
        temperature = float(temperature)
        if temperature not in self._sample_jit:
            cfg = self.cfg
            self._sample_jit[temperature] = jax.jit(
                lambda p, t, c, l, k: T.decode_and_sample(
                    cfg, p, t, c, l, temperature=temperature, key=k))
        return self._sample_jit[temperature]

    # -- streamed prefill -------------------------------------------------------

    @transfer_budget(d2h_arrays=0, d2h_outputs=())
    def _prefill_chunk_fn(self, chunk_len: int, first: bool, pos0: int):
        """jitted: process one prompt chunk against the running cache.

        ``pos0`` is static (chunk offsets are multiples of prefill_chunk) so
        the attention block-pair masks specialize per offset.
        """
        key = (chunk_len, first, pos0)

        def make():
            cfg = self.cfg
            has_prefix = first and cfg.prefix_len > 0

            def fn(params, caches, tokens, enc_out, prefix):
                h = T._embed_tokens(cfg, params, tokens)
                if has_prefix:
                    pre = prefix.astype(cfg.compute_dtype)
                    if cfg.embed_scale:
                        import math
                        pre = pre * jnp.asarray(
                            math.sqrt(cfg.d_model), cfg.compute_dtype)
                    h = jnp.concatenate([pre, h], axis=1)
                s = h.shape[1]
                if cfg.sinusoidal_pos:
                    from repro.models import layers as _l
                    h = h + _l.sinusoidal_positions(
                        pos0 + s, cfg.d_model, cfg.compute_dtype)[None, pos0:]
                positions = pos0 + jnp.arange(s)
                h, caches, _ = T.forward_hidden(
                    cfg, params, h, positions=positions, caches=caches,
                    enc_out=enc_out,
                    prefix_len=cfg.prefix_len if has_prefix else 0,
                    causal=True, q_offset=pos0)
                return T.head_logits(cfg, params, h[:, -1:]), caches

            return jax.jit(fn)

        return _lru_jit(self._chunk_jit, key, make, cap=self._chunk_jit_cap)

    @transfer_budget(d2h_arrays=0, d2h_outputs=())
    def _fused_chunk_fn(self, chunk_len: int, pos0: int):
        """jitted: one prompt chunk whose K/V projections are written
        directly into the pool's blocks through the page table (prefill →
        page-scatter fusion) instead of into a contiguous slab that a
        second jitted scatter copies.  Attention for the chunk reads the
        context back through the same table with the exact flash-chunk
        decomposition the contiguous path uses, so the pool contents are
        bitwise-identical to scatter-after-attention at fp32.

        A recurrent layer's per-slot state (SSM and conv rows, slot-indexed
        in the pool) is read from row ``slot`` (zeros on the first chunk)
        and written back there. Decoder archs only (no prefix embeds /
        encoder output): the engine gates on ``ServeConfig.fused_prefill``,
        which ``validate_arch`` refuses for whisper. The pools are donated
        and updated in place.
        """
        key = ("fused", chunk_len, pos0)

        def make():
            cfg = self.cfg

            def fn(params, pools, page_table, tokens, slot):
                h = T._embed_tokens(cfg, params, tokens)
                s = h.shape[1]
                if cfg.sinusoidal_pos:
                    from repro.models import layers as _l
                    h = h + _l.sinusoidal_positions(
                        pos0 + s, cfg.d_model, cfg.compute_dtype)[None, pos0:]
                positions = pos0 + jnp.arange(s)
                caches = _slot_rows(pools, slot, fresh=pos0 == 0)
                h, caches, _ = T.forward_hidden(
                    cfg, params, h, positions=positions, caches=caches,
                    causal=True, q_offset=pos0, page_table=page_table)
                pools = _put_slot_rows(pools, caches, slot)
                return T.head_logits(cfg, params, h[:, -1:]), pools

            return jax.jit(fn, donate_argnums=1)

        return _lru_jit(self._chunk_jit, key, make, cap=self._chunk_jit_cap)

    def prefill_streamed(
        self, tokens: jax.Array, *, enc_inputs=None, prefix_embeds=None
    ) -> tuple[jax.Array, Any, int]:
        """Process the prompt in ``prefill_chunk``-token tasks (streamed).

        Returns (last logits, caches, total prompt length incl. prefix).
        """
        logits, caches, pos = None, None, 0
        for logits, caches, pos in self.iter_prefill_chunks(
                tokens, enc_inputs=enc_inputs, prefix_embeds=prefix_embeds):
            pass
        return logits, caches, pos

    def iter_prefill_chunks(
        self, tokens: jax.Array, *, enc_inputs=None, prefix_embeds=None,
        caches=None, pos0: int = 0,
    ):
        """Generator form of the streamed prefill: yields after *dispatching*
        each chunk (JAX dispatch is async), so a caller can overlap other
        device work — the continuous-batching engine interleaves batched
        decode steps here — before the next chunk is enqueued.

        ``caches``/``pos0`` continue a prefill whose first ``pos0`` cache
        rows are already resident (prefix sharing: the SYNC prefix is staged
        once and only the uncovered tail streams).  The chunk grid stays
        anchored at absolute position 0 (the chunk size is picked from the
        *full* length ``pos0 + s``), so when ``pos0`` is a multiple of that
        chunk a continued prefill dispatches the exact same chunk tasks a
        full prefill would — token parity is bitwise, not approximate.

        Yields (logits-so-far, caches, position-after-chunk) per chunk.
        """
        cfg, scfg = self.cfg, self.scfg
        b, s = tokens.shape
        enc_out = (
            T.encode(cfg, self.params, enc_inputs) if enc_inputs is not None
            else None)
        if caches is None:
            assert pos0 == 0, "a continued prefill needs its context cache"
            caches = T.init_cache(
                cfg, b, scfg.max_seq,
                enc_seq=enc_out.shape[1] if enc_out is not None else None,
                ring=False)  # streamed prefill needs full-length caches
        # prefix (SYNC transfer) rides with the first chunk
        chunk = min(scfg.prefill_chunk, pos0 + s)
        pos = pos0
        first = pos0 == 0
        for lo in range(0, s, chunk):
            piece = tokens[:, lo: lo + chunk]
            fn = self._prefill_chunk_fn(piece.shape[1], first, pos)
            logits, caches = fn(
                self.params, caches, piece, enc_out,
                prefix_embeds if first else None)
            pos += piece.shape[1] + (cfg.prefix_len if first and
                                     prefix_embeds is not None else 0)
            first = False
            yield logits, caches, pos

    # -- decode -------------------------------------------------------------------

    def generate(
        self, tokens: jax.Array, *, enc_inputs=None, prefix_embeds=None,
        key=None,
    ) -> jax.Array:
        """Greedy/temperature decode after a streamed prefill.

        Sampling runs on device inside the jitted decode step (fused
        argmax/categorical), so the loop moves (B,) int32 tokens between
        steps, never the (B, vocab) logits.
        """
        logits, caches, pos = self.prefill_streamed(
            tokens, enc_inputs=enc_inputs, prefix_embeds=prefix_embeds)
        temp = self.scfg.temperature
        key = key if key is not None else jax.random.PRNGKey(0)
        if temp > 0.0:
            key, sub = jax.random.split(key)
        else:
            sub = key
        nxt = T.sample_tokens(logits[:, -1], temperature=temp, key=sub)
        out = [nxt[:, None]]
        fused = self._decode_sample_fn(temp)
        for i in range(self.scfg.max_new_tokens - 1):
            if temp > 0.0:
                key, sub = jax.random.split(key)
            nxt, caches = fused(
                self.params, nxt[:, None], caches, jnp.int32(pos + i), sub)
            out.append(nxt[:, None])
        return jnp.concatenate(out, axis=1)


# ----------------------------------------------------------------------------
# Continuous batching: request queue + slot manager over one batched cache.
# ----------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    """One Independent task (paper §4.1) in the serving queue."""

    uid: int
    tokens: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int
    enc_inputs: np.ndarray | None = None  # encoder-decoder only: this
    # request's encoded frames (1, encoder_seq, d_model) — the SYNC stage
    # input, staged once at admission
    t_submit: float = 0.0  # perf_counter at submit(); queue wait (and the
    # submit->first-token TTFT the SLO policy scores) is measured from here


@dataclasses.dataclass
class _Slot:
    """Decode-batch slot bookkeeping (positions live here, not in the cache)."""

    index: int
    uid: int | None = None  # None = free
    cur: int = 0  # absolute cache position of the next KV write
    pending: int = 0  # last sampled token (decode input)
    emitted: list[int] = dataclasses.field(default_factory=list)
    max_new: int = 0
    seq: int = 0  # admission order (newest is preempted first)
    prompt: np.ndarray | None = None  # prompt tokens: the drafter's lookup
    # corpus, and the readmission prefix re-map's registry key
    # Per-request latency bookkeeping (host floats only; the SLO policy
    # scores these at reap, and evict/readmit carries them unchanged):
    ttft_s: float = 0.0  # submit -> first token (queue wait + admission)
    t_last: float = 0.0  # perf_counter of the request's last emitted token
    itl_max: float = 0.0  # worst per-token inter-token latency so far —
    # a stall (evict -> readmit wait) lands here, which is the point
    evictions: int = 0  # times this request was preempted mid-decode

    @property
    def free(self) -> bool:
        return self.uid is None

    @property
    def done(self) -> bool:
        return self.uid is not None and len(self.emitted) >= self.max_new


@dataclasses.dataclass
class EvictedRequest:
    """A preempted request: cache rows + positions, ready to readmit."""

    uid: int
    caches: Any  # (layers, 1, S, ...) b=1 cache (S = max_seq, or the gathered
    # page span n_pages * block_size when evicted from the paged engine)
    cur: int
    pending: int
    emitted: list[int]
    max_new: int
    n_pages: int = 0  # pages gathered (0 = contiguous eviction)
    seq: int = 0  # original admission order — restored on readmit so a
    # preempted request never becomes the "youngest" (preemption victim) again
    prompt: np.ndarray | None = None  # prompt tokens, carried so readmission
    # can re-map a registered shared prefix at refcount+1 instead of
    # re-scattering exclusive pages (and so the drafter keeps its corpus)
    # Latency bookkeeping rides through the evict->readmit cycle so the
    # reap-time SLO score sees the whole request, stall included:
    ttft_s: float = 0.0
    t_last: float = 0.0
    itl_max: float = 0.0
    evictions: int = 0


@dataclasses.dataclass(frozen=True)
class ServingPlan:
    """Chunk/interleave/page-size policy from the paper's generic flow."""

    decision: str  # streams.plan_streaming decision string
    prefill_chunk: int
    decode_interleave: int
    stage_times: rmetric.StageTimes
    block_size: int = 16  # KV page granularity for the paged cache

    def __post_init__(self) -> None:
        # A plan is a contract: PagedKVCache/ServeConfig would reject these,
        # so refuse to emit them in the first place.
        for field in ("prefill_chunk", "decode_interleave", "block_size"):
            if getattr(self, field) < 1:
                raise ValueError(
                    f"invalid plan: {field} must be >= 1, got "
                    f"{getattr(self, field)}")


def plan_block_size(
    stage_times: rmetric.StageTimes, *, prefill_chunk: int,
    max_seq: int | None = None, min_block: int = 8, max_block: int = 128,
) -> int:
    """Size the KV page from the same stage measurements that size chunks.

    A page is the Independent transfer task of the paged cache (its
    allocation, prefill scatter and decode writes move page-at-a-time), so
    the paper's depth primitive applies: split one prefill chunk's KV into
    ``optimal_streams`` page-tasks.  When streaming isn't worthwhile (R
    below the gate) per-page management overhead buys nothing, so pages go
    as coarse as allowed — the same overhead-vs-overlap trade the R gate
    arbitrates for chunks, at page granularity (the dominant knob in
    ML-guided tuning of streamed codes: Zhang et al.).
    """
    decision = rmetric.streaming_decision(stage_times)
    if decision is rmetric.StreamDecision.NOT_WORTHWHILE:
        n_tasks = 1
    else:
        n_tasks = rmetric.optimal_streams(stage_times, max_streams=8)
    target = max(min_block, prefill_chunk // max(1, n_tasks))
    block = 1 << (int(target).bit_length() - 1)  # largest pow2 <= target
    block = int(np.clip(block, min_block, max_block))
    if max_seq is not None:
        while block > min_block and max_seq % block != 0:
            block //= 2
        if max_seq % block != 0:
            # The pow2 search bottomed out at min_block without finding a
            # divisor (e.g. max_seq=100, min_block=8): PagedKVCache.__init__
            # would reject the plan.  Pages must tile the cache, so validity
            # beats the min_block preference — fall back to the largest real
            # divisor of max_seq at or below the granularity target.
            block = next(d for d in range(block, 0, -1) if max_seq % d == 0)
    return block


def plan_decode_policy(
    stage_times: rmetric.StageTimes, *, prompt_len: int,
    max_interleave: int = 8, min_chunk: int = 16, max_seq: int | None = None,
) -> ServingPlan:
    """Pick (prefill_chunk, decode_interleave, block_size) from measured
    stage times.

    ``stage_times``: h2d = one prefill chunk (the ingest stage of a new
    request), kex = one batched decode step (the steady compute stage).
    Requests are Independent tasks, so the paper's generic flow (§6)
    applies with its two primitives used directly: the R gate decides
    whether chunked-prefill interleaving is worthwhile at all, and
    ``optimal_streams`` picks the pipeline depth (number of prefill
    chunks); the interleave ratio equalizes the two stages so neither
    starves.  The KV page size rides on the same measurements
    (``plan_block_size``).
    """
    decision = rmetric.streaming_decision(stage_times)
    if decision is rmetric.StreamDecision.NOT_WORTHWHILE:
        # Chunk cost is negligible next to decode: interleaving buys nothing,
        # prefill in one task.
        chunk = max(min_chunk, prompt_len)
        return ServingPlan(
            decision.value, chunk, 1, stage_times,
            plan_block_size(stage_times, prefill_chunk=chunk,
                            max_seq=max_seq))
    if decision is rmetric.StreamDecision.STREAM:
        n_chunks = max(1, min(
            rmetric.optimal_streams(stage_times, max_streams=16),
            prompt_len // min_chunk))
    else:
        # R above the paper's band ("offload-unprofitable"): here it means a
        # prefill chunk dwarfs a decode step, so head-of-line blocking — not
        # offload cost — is the concern.  Chunk as finely as allowed and
        # interleave at the cap so active slots keep decoding underneath.
        n_chunks = max(1, prompt_len // min_chunk)
    chunk = max(min_chunk, -(-prompt_len // n_chunks))
    ratio = stage_times.h2d / max(stage_times.kex, 1e-9)
    interleave = int(np.clip(round(ratio), 1, max_interleave))
    return ServingPlan(
        decision.value, chunk, interleave, stage_times,
        plan_block_size(stage_times, prefill_chunk=chunk, max_seq=max_seq))


class _MetricAttr:
    """Data descriptor bridging a legacy counter attribute to the metrics
    registry.

    The engine's bench counters (``decode_steps``, ``prefix_hits``, ...)
    predate the registry; tests, benches and the profiler both read them
    and *assign* them (resetting to 0 between runs), so the shim must be
    a full data descriptor: reads come from ``engine.metrics``, writes go
    back into it.  Values keep whatever Python type the caller stored
    (ints stay ints).  New code should use ``engine.metrics`` /
    ``engine.metrics_snapshot()`` directly.
    """

    def __init__(self, metric: str):
        self.metric = metric

    def __get__(self, obj, objtype=None):
        if obj is None:
            return self
        return obj.metrics.value(self.metric)

    def __set__(self, obj, value):
        obj.metrics.set_value(self.metric, value)


class StreamedBatchEngine:
    """Continuous-batching streamed serving engine.

    Requests are admitted into ``max_batch`` slots of one batched KV cache;
    incoming prompts are prefilled in chunks interleaved with batched decode
    steps for the already-active slots (see module docstring).  Greedy
    decode output is token-identical to ``ServingEngine.generate`` per
    request.
    """

    # Legacy counter attributes, unified onto the metrics registry (one
    # snapshot via metrics_snapshot()); the bare names stay assignable.
    decode_steps = _MetricAttr("serving.decode_steps")
    peak_active = _MetricAttr("serving.peak_active")
    preemptions = _MetricAttr("serving.preemptions")
    admissions = _MetricAttr("serving.admissions")
    admit_seconds = _MetricAttr("serving.admit_seconds")
    prefix_hits = _MetricAttr("serving.prefix_hits")
    prefix_pages_shared = _MetricAttr("serving.prefix_pages_shared")
    snapshot_hits = _MetricAttr("serving.snapshot_hits")
    snapshot_tokens_reused = _MetricAttr("serving.snapshot_tokens_reused")
    readmit_prefix_hits = _MetricAttr("serving.readmit_prefix_hits")
    readmit_prefix_pages = _MetricAttr("serving.readmit_prefix_pages")
    spec_ticks = _MetricAttr("serving.spec_ticks")
    spec_proposed = _MetricAttr("serving.spec_proposed")
    spec_accepted = _MetricAttr("serving.spec_accepted")

    def __init__(self, cfg: ModelConfig, params: Any, scfg: ServeConfig,
                 *, plan: Any = None, drafter: Any = None,
                 tracer: Tracer | None = None,
                 metrics: MetricsRegistry | None = None,
                 slo: Any = None):
        # A TunedPlan (repro.tuning.db) — or anything with its ``apply``
        # contract — rewrites the streaming knobs (chunk, interleave, page
        # geometry, slot count, kernel path, compile-cache caps) before the
        # engine builds; duck-typed so the runtime never imports the tuner.
        if plan is not None:
            scfg = plan.apply(scfg)
        if scfg.max_batch < 1:
            raise ValueError(  # an empty slot pool would spin forever
                f"max_batch must be >= 1, got {scfg.max_batch}")
        # Everything architecture-specific — slot state layout, prefill
        # chain, decode step, what is shareable — lives behind the
        # servable (runtime.model_iface).  build_servable stamps
        # scfg.arch_kind, validates arch-dependent flags, and rejects
        # still-unserved archs (prefix-LM) before touching params.
        # Imported lazily: model_iface imports this module eagerly.
        from repro.runtime.model_iface import build_servable
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        # Observability (repro.obs): the registry backs every counter
        # below (set before them — the _MetricAttr descriptors route
        # through it); the tracer is a disabled stub unless the caller
        # wants spans, so the tick-path hooks cost one attribute check.
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.obs = tracer if tracer is not None else Tracer(enabled=False)
        self.slo = slo  # an obs.slo.SLOPolicy (duck-typed: ttft_ok/itl_ok/
        # met/as_dict); None = no per-request SLO scoring at reap
        self._tick_index = 0  # span ordinal (tick= arg on decode spans)
        self._budget_flagged = False  # live-STR002 warned once per engine
        self.servable = build_servable(cfg, params, scfg)
        self.metrics = self.metrics  # attach the servable's device counters
        self.single = self.servable.single  # b=1 prefill machinery
        b = scfg.max_batch
        self.paged = scfg.paged
        self.prefixes_restored = 0  # registry entries warm-started from
        # scfg.prefix_store (0 = cold start or stale/absent store)
        if self.paged:
            self.kv = self.servable.make_kv_pool()
            self.kv.metrics = self.metrics
            self.caches = None  # KV lives in self.kv.pools
            if scfg.prefix_sharing and scfg.prefix_store:
                self.prefixes_restored = self.kv.load_prefixes(
                    scfg.prefix_store)
        else:
            self.kv = None
            self.caches = self.servable.init_slot_caches(b)
        self.slots = [_Slot(index=i) for i in range(b)]
        self.queue: collections.deque[Request] = collections.deque()
        self._preempted: collections.deque[EvictedRequest] = (
            collections.deque())  # page-pressure victims awaiting readmission
        self.outputs: dict[int, np.ndarray] = {}
        self._next_uid = 0
        self._admit_seq = 0
        self._evicted_out = 0  # outstanding evictions (pin pool geometry)
        self.decode_steps = 0  # batched decode steps run (for benchmarks)
        self.peak_active = 0  # max concurrently-resident requests (bench)
        self.preemptions = 0  # page-pressure evictions (bench / regression)
        self.admissions = 0  # fresh admissions (readmit is bookkeeping)
        self.admit_seconds = 0.0  # end-to-end admission latency: queue-pop
        # to first sampled token, per request.  Interleaved decode ticks for
        # other slots ride along deliberately — they scale with the number
        # of prefill chunks, which is exactly what prefix sharing cuts.
        self.prefix_hits = 0  # admissions that mapped a shared prefix
        self.prefix_pages_shared = 0  # pages mapped instead of prefilled
        self.snapshot_hits = 0  # admissions that restored an SSM-state
        # snapshot (mamba state_snapshots — sharing's SSM degradation)
        self.snapshot_tokens_reused = 0  # prompt tokens never re-prefilled
        self.readmit_prefix_hits = 0  # readmissions that re-mapped their
        # registered prefix (pages shared again instead of re-scattered)
        self.readmit_prefix_pages = 0  # pages re-mapped on readmission
        self.spec_ticks = 0  # verify steps run (speculative decode)
        self.spec_proposed = 0  # draft tokens scored by verify steps
        self.spec_accepted = 0  # draft tokens accepted (rate = acc/prop)
        self.last_stage_times: rmetric.StageTimes | None = None  # newest
        # measure_stage_times probe — retained (not discarded after
        # planning) so callers (an online re-tuner, dashboards) can read
        # the measurement a decision was based on without re-probing
        self.last_plan: ServingPlan | None = None  # newest autotune plan
        self._gate_match: tuple[int, int, list[int], bool] | None = None
        # the admission gate's prefix match (uid, n_pages, blocks, probed),
        # handed to _admit (avoids a second lookup; valid because nothing
        # runs between gate and admission)

        # Decode step with on-device sampling fused in (the servable owns
        # the jit — see ServableModel.decode_fn): a tick moves one int32
        # per slot to the host, never the (B, vocab) logits.  With
        # temperature, per-slot keys are folded from (uid, step) on device.
        # A tick dispatches the engine's own step donated the pools
        # (updated in place); a step put in place of ``_decode_jit`` from
        # outside (a wrapper, an injected fault) may hand back the pools it
        # was given, so it is called as it is (see ``_decode_step``).
        self._decode_jit = self.servable.decode_fn(
            paged=self.paged, donate=False)
        self._decode_own = (self._decode_jit,
                            self.servable.decode_fn(paged=self.paged))
        # Scatter one request's (b=1) cache into slot i of the global cache /
        # gather it back out (contiguous path; the paged engine moves pages
        # through self.kv instead).  Slot index is traced, so one compile
        # serves every slot.
        self._scatter_jit = jax.jit(lambda g, l, i: jax.tree.map(
            lambda gg, ll: jax.lax.dynamic_update_slice_in_dim(
                gg, ll.astype(gg.dtype), i, axis=1), g, l))
        self._gather_jit = jax.jit(lambda g, i: jax.tree.map(
            lambda gg: jax.lax.dynamic_slice_in_dim(gg, i, 1, axis=1), g))
        # Speculative decode: the drafter proposes, one jitted verify step
        # (repro.runtime.spec) scores pending + spec_k positions per slot
        # and accepts on device; ticks advance by a variable token count.
        self.drafter = None
        self._spec_jit = None
        if scfg.spec_decode:
            from repro.runtime import spec as _spec
            self.drafter = (drafter if drafter is not None
                            else _spec.NGramDrafter(max_n=scfg.spec_ngram))
            self._spec_jit = self.servable.make_verifier(paged=self.paged)
        # Runtime transfer accounting — the dynamic twin of the analyzer's
        # static STR002 audit: the declared @transfer_budget of the step
        # builders actually used, checked per tick against fetched bytes
        # while tracing is enabled (see _account_tick).
        self._decode_budget = budget_of(self.servable.decode_fn)
        self._verify_budget = (budget_of(self.servable.make_verifier)
                               if scfg.spec_decode else None)

    @property
    def metrics(self) -> MetricsRegistry:
        return self._metrics

    @metrics.setter
    def metrics(self, registry: MetricsRegistry) -> None:
        """Install ``registry``; the servable's counters kept on the device
        restart from zero and are read into it at each snapshot."""
        self._metrics = registry
        if getattr(self, "kv", None) is not None:
            self.kv.metrics = registry
        servable = getattr(self, "servable", None)
        if servable is not None and servable.device_counters is not None:
            servable.reset_device_counters()
            registry.add_source(servable.read_device_counters)

    # -- queue ----------------------------------------------------------------

    def submit(self, tokens, max_new_tokens: int | None = None,
               *, enc_inputs=None) -> int:
        """Queue one prompt; returns its uid.  ``enc_inputs`` carries the
        per-request encoder input for encoder-decoder servables (rejected
        elsewhere — the servable validates)."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if tokens.size == 0:
            raise ValueError("prompt must contain at least one token")
        enc_inputs = self.servable.validate_request(tokens, enc_inputs)
        max_new = (self.scfg.max_new_tokens if max_new_tokens is None
                   else max_new_tokens)
        if max_new < 1:
            raise ValueError(  # admission always samples one token
                f"max_new_tokens must be >= 1, got {max_new}")
        if len(tokens) + max_new > self.scfg.max_seq:
            raise ValueError(
                f"prompt {len(tokens)} + max_new {max_new} exceeds "
                f"max_seq {self.scfg.max_seq}")
        if self.paged:
            # A request must be able to finish alone in the pool — the
            # progress guarantee behind backpressure and preemption.
            worst = self.kv.pages_for(len(tokens) + max_new)
            if worst > self.kv.allocator.capacity:
                raise ValueError(
                    f"request needs {worst} pages at worst but the pool has "
                    f"{self.kv.allocator.capacity}; grow num_blocks or "
                    f"shrink the request")
        uid = self._next_uid
        self._next_uid += 1
        self.queue.append(Request(uid, tokens, max_new, enc_inputs,
                                  t_submit=time.perf_counter()))
        return uid

    @property
    def active_slots(self) -> list[_Slot]:
        return [s for s in self.slots if not s.free]

    @property
    def pending(self) -> bool:
        return (bool(self.queue) or bool(self.active_slots)
                or bool(self._preempted))

    # -- slot plumbing ---------------------------------------------------------

    @staticmethod
    def _slot_key(uid: int, step: int) -> jax.Array:
        """Sampling key derived from (uid, step) so a request's draws don't
        depend on batch composition."""
        return slot_key(uid, step)

    @tick_path(allowed_fetches=1)
    def _sample(self, logits: jax.Array, uid: int, step: int) -> int:
        """Per-request sampling of the last position of ``logits``
        (1, S, vocab): greedy, or temperature via the slot key.

        The pick is reduced on device, then fetched once via the declared
        ``host_fetch`` — ``int()`` straight on the device scalar was the
        hidden-sync shape STR001 exists to catch.
        """
        dt0 = self.obs.begin("dispatch")
        logits_row = logits[0, -1]
        if self.scfg.temperature > 0.0:
            pick = jax.random.categorical(
                self._slot_key(uid, step),
                logits_row / self.scfg.temperature)
        else:
            pick = jnp.argmax(logits_row, axis=-1)
        self.obs.add("host", "dispatch", dt0, uid=uid)
        ft0 = self.obs.begin("fetch_wait")
        val = host_fetch(pick)
        self.obs.add("host", "fetch_wait", ft0, uid=uid)
        self.metrics.inc("transfer.d2h_bytes", int(val.nbytes))
        return int(val)

    @tick_path(allowed_fetches=0)
    def _admit(self, req: Request, slot: _Slot) -> None:
        """Chunked prefill of ``req`` interleaved with batched decode steps,
        then scatter its cache into ``slot``'s rows (contiguous) or pages
        (paged; the pages are reserved up front so the interleaved ticks'
        lazy allocation can never steal them).

        With ``prefix_sharing`` the longest registered page-aligned prefix
        of the prompt is mapped straight into the slot's page table at
        refcount+1 (the SYNC transfer staged once, §4.1) and only the
        uncovered tail is prefilled; matches are restricted to multiples of
        the prompt's chunk size so the tail re-runs the exact chunk tasks a
        full prefill would (bitwise token parity with the unshared path).
        """
        t0 = time.perf_counter()
        ot0 = self.obs.begin("admit")
        # Host phases of the admission (``host`` track, uid=): ``stage``
        # (page reservation, token upload, per-chunk table and lookup,
        # publish), ``dispatch``, ``fetch_wait`` of the first token, then
        # ``emit`` up to the close of the ``admit`` span.
        st0 = self.obs.begin("stage")
        # Queue wait: submit -> queue pop.  Direct _admit calls (tests)
        # carry no submit stamp; they waited nothing.
        queue_wait = max(0.0, t0 - req.t_submit) if req.t_submit else 0.0
        self.metrics.observe("latency.queue_wait_s", queue_wait)
        n_chunks = 0  # chunk tasks dispatched (span arg; overlap recon)
        shared_pages = 0
        if self.paged:
            if self.scfg.prefix_sharing:
                if self._gate_match and self._gate_match[0] == req.uid:
                    _, shared_pages, blocks, probed = self._gate_match
                else:  # direct _admit call (tests): no gate ran
                    shared_pages, blocks, probed = self._lookup_prefix(req)
                self._gate_match = None
                # One counted outcome per admission (the gate's repeated
                # polls are uncounted) — and none for prompts the descent
                # never probed (too short for an aligned proper prefix).
                if probed:
                    self.kv.registry.record_lookup(bool(shared_pages))
                if shared_pages:
                    self.kv.map_shared(slot.index, blocks)
                    self.prefix_hits += 1
                    self.prefix_pages_shared += shared_pages
            # Reserve through the *first decode write* (len + 1): reserving
            # only the prompt pages would pay the full prefill and then
            # fault (and likely bounce) on the very next tick whenever the
            # prompt is page-aligned — the same off-by-one as readmit's.
            ok = self.kv.alloc(slot.index, len(req.tokens) + 1)
            assert ok, "admission checked free pages before popping the queue"
            # Until the slot goes active it is a padding row of the
            # interleaved decode ticks below: its garbage writes must go to
            # the trash block, not into the reserved (possibly shared) pages.
            self.kv.shield(slot.index)
        shared_len = shared_pages * self.scfg.block_size
        use_fused = self.paged and bool(self.scfg.fused_prefill)
        caches0 = None
        if shared_len and not use_fused:
            # The tail's b=1 prefill context: shared pages gathered into the
            # front of a fresh full-length cache.  The pool pages themselves
            # are never rewritten — the slot reads them through its table.
            caches0 = self.kv.load_prefix(
                self.servable.init_request_cache(),
                self.kv.slot_pages(slot.index)[:shared_pages])
        elif not use_fused and self.servable.snapshots is not None:
            # The SSM degradation of prefix sharing: restore the longest
            # chunk-aligned state snapshot of the prompt and stream only
            # the uncovered tail (same chunk-grid parity argument as the
            # page path — the resumed prefill dispatches identical tasks).
            n, caches0 = self.servable.lookup_snapshot(req.tokens)
            if n:
                shared_len = n
                self.snapshot_hits += 1
                self.snapshot_tokens_reused += n
        ht0 = self.obs.begin("h2d_stage")
        tokens = jnp.asarray(req.tokens[None, shared_len:], jnp.int32)
        self.obs.add("transfer", "h2d_stage", ht0, uid=req.uid,
                     h2d_bytes=int(len(req.tokens) - shared_len) * 4)
        logits = caches = None
        pos = shared_len
        if use_fused:
            # Fused prefill→page-scatter: each chunk's K/V projections are
            # written straight into the slot's pool blocks through its page
            # table — no contiguous slab, no second jitted scatter, and a
            # shared prefix is read back through the same table instead of
            # being gathered into a private context first.  The *host* table
            # row carries the real pages (the device row stays shielded so
            # the interleaved ticks' padding writes keep going to trash);
            # only the pages covering the context so far ride along, so the
            # compiled shapes depend on (chunk_len, pos0) alone.
            row = np.full((1, self.kv.max_pages), 0, np.int32)
            own = self.kv.slot_pages(slot.index)
            row[0, : len(own)] = own
            slot_index = jnp.int32(slot.index)
        self.obs.add("host", "stage", st0, uid=req.uid)
        if use_fused:
            s_total = tokens.shape[1]
            # Same chunk grid as iter_prefill_chunks (anchored at absolute
            # position 0), so the fused path dispatches the exact chunk
            # tasks the legacy path would — fp32 parity is bitwise.
            chunk = min(self.scfg.prefill_chunk, shared_len + s_total)
            for lo in range(0, s_total, chunk):
                ct0 = self.obs.begin("prefill_chunk")
                st0 = self.obs.begin("stage")
                piece = tokens[:, lo: lo + chunk]
                n_ctx = self.kv.pages_for(pos + piece.shape[1])
                fn = self.single._fused_chunk_fn(piece.shape[1], pos)
                table = jnp.asarray(row[:, :n_ctx])
                self.obs.add("host", "stage", st0, uid=req.uid)
                dt0 = self.obs.begin("dispatch")
                logits, pools = fn(
                    self.params, self.kv.pools, table, piece, slot_index)
                self.kv.adopt(pools)
                self.obs.add("host", "dispatch", dt0, uid=req.uid)
                pos += piece.shape[1]
                n_chunks += 1
                # Chunk is dispatched (async); decode the active slots while
                # it is in flight — same overlap as the legacy path.
                for _ in range(self.scfg.decode_interleave):
                    if self.active_slots:
                        self._decode_tick()
                # The span is the chunk's in-flight window (dispatch through
                # the interleaved ticks), not its compute time — decode
                # spans landing inside it are transfer time hidden.
                self.obs.add("prefill", "prefill_chunk", ct0,
                             uid=req.uid, pos=pos, fused=True)
        else:
            # As many chunks as the generator's grid (the fused loop's)
            # holds, so that no span opens after the last one.
            s_total = tokens.shape[1]
            left = -(-s_total // min(self.scfg.prefill_chunk,
                                     shared_len + s_total))
            ct0 = self.obs.begin("prefill_chunk")
            dt0 = self.obs.begin("dispatch")
            for logits, caches, pos in self.servable.iter_prefill_chunks(
                    req, tokens, caches=caches0, pos0=shared_len):
                self.obs.add("host", "dispatch", dt0, uid=req.uid)
                self.servable.maybe_snapshot(req.tokens, caches, pos)
                n_chunks += 1
                # Chunk is dispatched (async); decode the active slots while
                # it is in flight — prefill chunk t+1 overlapping decode
                # compute.
                for _ in range(self.scfg.decode_interleave):
                    if self.active_slots:
                        self._decode_tick()
                # In-flight window span (see the fused loop above).
                self.obs.add("prefill", "prefill_chunk", ct0,
                             uid=req.uid, pos=pos, fused=False)
                left -= 1
                if left:  # the next chunk's span opens here
                    ct0 = self.obs.begin("prefill_chunk")
                    dt0 = self.obs.begin("dispatch")
        st0 = self.obs.begin("stage")
        if self.paged:
            if not use_fused:  # fused chunks already wrote the pool blocks
                pt0 = self.obs.begin("page_scatter")
                self.kv.scatter(
                    slot.index, caches, pos, start_page=shared_pages)
                self.obs.add("transfer", "page_scatter", pt0, uid=req.uid,
                             pages=int(self.kv.pages_for(pos)
                                       - shared_pages),
                             state_bytes=self.kv.slot_state_bytes)
            self.kv.publish(slot.index)
            if self.scfg.prefix_sharing:
                self.kv.register_prefix(
                    req.tokens, slot.index,
                    min_pages=self.scfg.prefix_min_pages,
                    align_tokens=self.scfg.prefill_chunk)
        else:
            pt0 = self.obs.begin("slot_scatter")
            self.caches = self._scatter_jit(
                self.caches, caches, jnp.int32(slot.index))
            self.obs.add("transfer", "slot_scatter", pt0, uid=req.uid)
        self.obs.add("host", "stage", st0, uid=req.uid)
        first = self._sample(logits, req.uid, 0)
        et0 = self.obs.begin("emit")
        slot.uid = req.uid
        slot.cur = pos
        slot.pending = first
        slot.emitted = [first]
        slot.max_new = req.max_new_tokens
        slot.prompt = req.tokens
        slot.seq = self._admit_seq
        self._admit_seq += 1
        self.peak_active = max(self.peak_active, len(self.active_slots))
        self.admissions += 1
        dt = time.perf_counter() - t0
        self.admit_seconds += dt
        slot.ttft_s = queue_wait + dt  # the SLO policy's TTFT: from submit
        slot.t_last = t0 + dt  # ITL clock starts at the first token
        slot.itl_max = 0.0
        slot.evictions = 0
        self.metrics.observe("latency.ttft_s", dt)
        self.metrics.inc("serving.tokens_emitted", 1)  # the first token
        self.obs.add("host", "emit", et0, uid=req.uid)
        self.obs.add("prefill", "admit", ot0, uid=req.uid, chunks=n_chunks,
                     shared_len=shared_len, prompt_len=len(req.tokens),
                     slot=slot.index, queue_wait_s=queue_wait,
                     max_new=req.max_new_tokens)
        self._reap(slot)

    def _reap(self, slot: _Slot) -> None:
        """Free a finished slot (and its pages) and record its output;
        with an ``slo`` policy, score the finished request here (the one
        place every request passes through exactly once)."""
        if slot.done:
            if self.slo is not None:
                m = self.metrics
                m.inc("slo.requests")
                if self.slo.met(ttft_s=slot.ttft_s, itl_s=slot.itl_max):
                    m.inc("slo.requests_met")
                    # Goodput: only tokens from SLO-met requests count.
                    m.inc("slo.goodput_tokens", len(slot.emitted))
                else:
                    if not self.slo.ttft_ok(slot.ttft_s):
                        m.inc("slo.ttft_violations")
                    if not self.slo.itl_ok(slot.itl_max):
                        m.inc("slo.itl_violations")
            self.outputs[slot.uid] = np.asarray(slot.emitted, np.int32)
            slot.uid = None
            slot.emitted = []
            slot.prompt = None
            if self.paged:
                self.kv.release(slot.index)

    def _preempt_for_pages(self, protect: frozenset[int]) -> bool:
        """Evict the youngest active slot (outside ``protect``) back to the
        preempted queue, freeing its pages.  False = nobody to preempt."""
        victims = [s for s in self.active_slots if s.index not in protect]
        if not victims:
            return False
        victim = max(victims, key=lambda s: s.seq)
        self._preempted.append(self.evict(victim.uid))
        self.preemptions += 1
        return True

    def _lookup_prefix(self, req: Request) -> tuple[int, list[int], bool]:
        """Shared-prefix match for ``req`` -> (n_pages, blocks, probed);
        (0, [], False) without sharing.  The lookup also LRU-bumps the
        matched entry, protecting it from the reclaim the admission gate
        may run next.  Uncounted (``count=False``): the gate re-runs it
        every scheduling quantum a backpressured request waits, so the
        single hit-or-miss per admission is recorded in ``_admit`` instead
        (``probed`` rides along so a prompt the descent never probed —
        too short for an aligned proper prefix — records nothing)."""
        if not (self.paged and self.scfg.prefix_sharing):
            return 0, [], False
        chunk = min(self.scfg.prefill_chunk, len(req.tokens))
        n, blocks = self.kv.lookup_prefix(
            req.tokens, min_pages=self.scfg.prefix_min_pages,
            align_tokens=chunk, count=False)
        return n, blocks, self.kv.last_lookup_probed

    @tick_path(allowed_fetches=0)
    def _admission_fits(self, req: Request) -> bool:
        """Admission gate: can ``req`` take a slot right now?  Counts pages
        through the first decode write (len + 1), credits a shared-prefix
        match (mapped, not allocated), and reclaims retained prefixes when
        still short.  Re-checks after reclaiming because reclaim may have
        dropped the matched entry itself.  The surviving match is stashed
        for ``_admit`` so the admission doesn't repeat the lookup."""
        st0 = self.obs.begin("schedule")
        full = self.kv.pages_for(len(req.tokens) + 1)
        fits = False
        for _ in range(3):  # match -> reclaim -> match-dropped converges
            n, blocks, probed = self._lookup_prefix(req)
            if full - n <= self.kv.free_pages:
                self._gate_match = (req.uid, n, blocks, probed)
                fits = True
                break
            if not self.kv.reclaim_for(full - n):
                break
        self.obs.add("host", "schedule", st0)
        return fits

    @tick_path(allowed_fetches=0)
    def _decode_tick(self) -> None:
        """One decode tick: speculative (draft + batched verify) when
        ``spec_decode`` is on, else one plain batched single-token step."""
        if self.scfg.spec_decode:
            return self._spec_tick()
        return self._plain_tick()

    @tick_path(allowed_fetches=0)
    def _fault_base_positions(self) -> None:
        """Lazy page fault: make each active slot's write position
        resident, preempting the youngest slots if the pool runs dry
        (oldest-first service keeps the progress guarantee).  When no
        other slot is left to victimize — e.g. the rest of the pool is
        reserved by an admission's in-flight prefill — the faulting
        slot preempts itself and waits for pages.  (Shared by the plain
        and the speculative tick: one fault/preempt policy.)  Its
        ``page_fault`` span carries the number of the tick it precedes."""
        pt0 = self.obs.begin("page_fault")
        for s in sorted(self.active_slots, key=lambda s: s.seq):
            if s.uid is None:
                continue  # preempted by an earlier iteration
            while not self.kv.ensure_write(s.index, s.cur):
                if not self._preempt_for_pages(frozenset({s.index})):
                    self._preempted.append(self.evict(s.uid))
                    self.preemptions += 1
                    break
        self.obs.add("host", "page_fault", pt0, tick=self._tick_index + 1)

    def _account_tick(self, name: str, ot0: int, dt: float, *,
                      emit_t0: int, n_slots: int, new_tokens: int,
                      d2h_bytes: int, h2d_bytes: int, budget: Any,
                      attrib: dict[str, Any] | None = None) -> None:
        """Per-tick bookkeeping shared by the plain and speculative ticks:
        metrics (token/byte counters, per-tick transfer histograms), the
        tick's ``emit`` phase (opened at ``emit_t0``) and then its
        decode-track span (``attrib`` carries the per-slot uid/token
        attribution lists when tracing is on), and runtime transfer
        accounting — fetched bytes checked against the step builder's
        declared ``@transfer_budget`` while tracing is on, with excess
        flagged as a *live* STR002 (counter + trace marker + one warning
        per engine).  All values are host-side by the time they arrive
        here, so this never syncs the device.  (ITL is observed per slot
        in the tick loops, where the per-request emit clock lives.)"""
        m = self.metrics
        m.inc("serving.tokens_emitted", new_tokens)
        m.inc("time.tick_seconds", dt)
        m.inc("transfer.d2h_bytes", d2h_bytes)
        m.inc("transfer.h2d_bytes", h2d_bytes)
        m.observe("transfer.d2h_bytes_per_tick", d2h_bytes)
        self._tick_index += 1
        self.obs.add("host", "emit", emit_t0, tick=self._tick_index)
        self.obs.add("decode", name, ot0, tick=self._tick_index,
                     slots=n_slots, tokens=new_tokens,
                     d2h_bytes=d2h_bytes, h2d_bytes=h2d_bytes,
                     **(attrib or {}))
        if budget is not None and self.obs.enabled:
            limit = budget.bytes_limit(self.scfg)
            if limit is not None and d2h_bytes > limit * self.scfg.max_batch:
                m.inc("analysis.str002_live")
                self.obs.instant(
                    "transfer", "STR002", tick=self._tick_index,
                    d2h_bytes=d2h_bytes,
                    limit=int(limit) * self.scfg.max_batch)
                if not self._budget_flagged:
                    self._budget_flagged = True
                    warnings.warn(
                        f"STR002 (live): {name} fetched {d2h_bytes} B this "
                        f"tick, over the declared @transfer_budget of "
                        f"{int(limit) * self.scfg.max_batch} B "
                        f"({int(limit)} B/slot x {self.scfg.max_batch} "
                        "slots)", RuntimeWarning, stacklevel=3)

    def metrics_snapshot(self) -> dict[str, Any]:
        """The engine's telemetry in one JSON-serializable dict.

        ``counters``/``histograms`` come straight from the registry (the
        catalog is in the README's Observability section); ``derived``
        adds the rates the benches report — tokens/s over engine wall
        time, spec acceptance, prefix/snapshot hit rates, and the paged
        pool's utilization stats.
        """
        snap = self.metrics.snapshot()
        c = snap["counters"]
        tokens = c.get("serving.tokens_emitted", 0)
        wall = (c.get("time.tick_seconds", 0.0)
                + c.get("serving.admit_seconds", 0.0))
        admissions = c.get("serving.admissions", 0)
        proposed = c.get("serving.spec_proposed", 0)
        derived: dict[str, Any] = {
            "tokens_per_s": tokens / wall if wall > 0 else 0.0,
            "spec_acceptance": (c.get("serving.spec_accepted", 0) / proposed
                                if proposed else 0.0),
            "prefix_hit_rate": (c.get("serving.prefix_hits", 0) / admissions
                                if admissions else 0.0),
            "snapshot_hit_rate": (c.get("serving.snapshot_hits", 0)
                                  / admissions if admissions else 0.0),
        }
        if self.slo is not None:
            done = c.get("slo.requests", 0)
            met = c.get("slo.requests_met", 0)
            derived["slo"] = {
                "policy": self.slo.as_dict(),
                "requests": done,
                "met": met,
                "attainment": met / done if done else 0.0,
                # Goodput: tokens/s counting only SLO-met requests' tokens
                # over the same engine wall time as tokens_per_s.
                "goodput_tokens_per_s": (
                    c.get("slo.goodput_tokens", 0) / wall
                    if wall > 0 else 0.0),
                "ttft_violations": c.get("slo.ttft_violations", 0),
                "itl_violations": c.get("slo.itl_violations", 0),
            }
        if self.paged:
            st = self.kv.stats(active_slots=len(self.active_slots))
            derived["pool"] = {
                "capacity": st.capacity,
                "in_use": st.in_use,
                "peak_in_use": st.peak_in_use,
                "utilization": st.utilization,
                "page_bytes": st.page_bytes,
                "bytes_in_use": st.bytes_in_use,
            }
        snap["derived"] = derived
        return snap

    def _decode_step(self):
        """The decode program a tick dispatches: the engine's own step,
        donated the pools, or whatever was put in place of
        ``_decode_jit``, undonated (its runs count as
        ``serving.pool_copied_runs``)."""
        own, donated = self._decode_own
        return donated if self._decode_jit is own else self._decode_jit

    @tick_path(allowed_fetches=1)
    def _plain_tick(self) -> None:
        """One batched decode step for all slots (inactive rows are padding).

        Sampling is fused into the jitted step: the only device-to-host
        transfer per tick is the (B,) int32 of sampled tokens.
        """
        if self.paged:
            self._fault_base_positions()
        act = self.active_slots
        if not act:
            return
        # Host phases (``host`` track, the tick's number): ``page_fault``
        # above, then ``stage``, ``dispatch``, ``fetch_wait`` and ``emit``.
        tick = self._tick_index + 1
        ot0 = self.obs.begin("decode_tick")
        t0 = time.perf_counter()
        st0 = self.obs.begin("stage")
        b = self.scfg.max_batch
        toks = np.zeros((b, 1), np.int32)
        cur = np.zeros((b,), np.int32)
        for s in act:
            toks[s.index, 0] = s.pending
            cur[s.index] = s.cur
        h2d_bytes = int(toks.nbytes) + int(cur.nbytes)
        args = [self.params, jnp.asarray(toks)]
        if self.paged:
            args += [self.kv.pools, self.kv.device_page_table()]
        else:
            args += [self.caches]
        args += [jnp.asarray(cur)]
        if self.scfg.temperature > 0.0:
            uids = np.zeros((b,), np.int32)
            steps = np.zeros((b,), np.int32)
            for s in act:
                uids[s.index] = s.uid
                steps[s.index] = len(s.emitted)
            args += [jnp.asarray(uids), jnp.asarray(steps)]
        self.obs.add("host", "stage", st0, tick=tick)
        dt0 = self.obs.begin("dispatch")
        nxt, new_caches = self._decode_step()(*args)
        if self.paged:
            self.kv.adopt(new_caches)
        else:
            self.caches = new_caches
        self.decode_steps += 1
        self.obs.add("host", "dispatch", dt0, tick=tick)
        ft0 = self.obs.begin("fetch_wait")
        picks = host_fetch(nxt)  # (B,) int32 — the tick's only D2H
        t1 = time.perf_counter()
        self.obs.add("host", "fetch_wait", ft0, tick=tick)
        et0 = self.obs.begin("emit")
        attrib = (dict(uids=[s.uid for s in act], toks=[1] * len(act))
                  if self.obs.enabled else None)
        for s in act:
            s.cur += 1
            s.pending = int(picks[s.index])
            s.emitted.append(int(picks[s.index]))
            # Per-request ITL: time since this slot's previous emitted
            # token (a readmitted slot's first tick absorbs its stall).
            gap = t1 - s.t_last
            s.t_last = t1
            if gap > s.itl_max:
                s.itl_max = gap
            self.metrics.observe("latency.itl_s", gap)
            self._reap(s)
        self._account_tick(
            "decode_tick", ot0, t1 - t0, emit_t0=et0,
            n_slots=len(act), new_tokens=len(act),
            d2h_bytes=int(picks.nbytes), h2d_bytes=h2d_bytes,
            budget=self._decode_budget, attrib=attrib)

    # -- speculative decode ----------------------------------------------------

    def _spec_budget(self, s: _Slot) -> int:
        """Draft tokens worth proposing for ``s`` this tick: capped by the
        remaining token budget (a tick emits at most budget + 1 tokens) and
        by the cache rows left for the draft block's writes."""
        return max(0, min(self.scfg.spec_k,
                          s.max_new - len(s.emitted) - 1,
                          self.scfg.max_seq - 1 - s.cur))

    @tick_path(allowed_fetches=2)
    def _spec_tick(self) -> None:
        """One speculate/verify step: the drafter proposes up to ``spec_k``
        tokens per slot, one jitted multi-token target step scores all
        ``k + 1`` positions, and each slot advances by its accepted prefix
        plus the bonus token — a *variable* number of tokens per tick (the
        chunked decode stream that makes the ITERATIVE category streamable).

        Paged residency: the base position faults exactly like the plain
        tick (preempting under pressure), but draft positions are
        best-effort — a slot never preempts a neighbor just to speculate;
        its draft shrinks to the pages available.  After acceptance the
        pages covering rejected positions are rolled back to the free list
        (``kv.truncate``); ``ensure_write`` COW-forks any shared target
        first, so shared prefix pages are never corrupted and never freed.
        """
        k = self.scfg.spec_k
        if self.paged:
            self._fault_base_positions()
        act = self.active_slots
        if not act:
            return
        tick = self._tick_index + 1
        ot0 = self.obs.begin("spec_tick")
        t0 = time.perf_counter()
        b = self.scfg.max_batch
        toks = np.zeros((b, k + 1), np.int32)
        cur = np.zeros((b,), np.int32)
        d_len = np.zeros((b,), np.int32)
        dt0 = self.obs.begin("spec_draft")
        for s in act:
            toks[s.index, 0] = s.pending
            cur[s.index] = s.cur
            budget = self._spec_budget(s)
            draft = np.zeros(0, np.int32)
            if budget > 0:
                draft = np.asarray(self.drafter.propose(
                    np.concatenate([np.asarray(s.prompt, np.int32),
                                    np.asarray(s.emitted, np.int32)]),
                    budget), np.int32)[:budget]
            if self.paged and draft.size:
                # Extend residency over the draft block without preempting
                # anyone; on shortfall the draft shrinks to what fits.
                have = draft.size
                for pos in range(s.cur + 1, s.cur + draft.size + 1):
                    if not self.kv.ensure_write(s.index, pos):
                        have = pos - s.cur - 1
                        break
                draft = draft[:have]
            if draft.size:
                toks[s.index, 1: 1 + draft.size] = draft
                d_len[s.index] = draft.size
                self.spec_proposed += int(draft.size)
        self.obs.add("decode", "spec_draft", dt0,
                     proposed=int(d_len.sum()),
                     **(dict(uids=[s.uid for s in act],
                             drafted=[int(d_len[s.index]) for s in act])
                        if self.obs.enabled else {}))
        if not int(d_len.sum()):
            # Every drafter came back empty (lookup miss, or the slots are
            # at their final token): the k+1-wide verify step would pay
            # ~(k+1)x a plain tick's compute with zero possible acceptance
            # — dispatch the already-compiled single-token step instead.
            return self._plain_tick()
        st0 = self.obs.begin("stage")
        args = [self.params, jnp.asarray(toks)]
        if self.paged:
            args += [self.kv.pools, self.kv.device_page_table()]
        else:
            args += [self.caches]
        args += [jnp.asarray(cur), jnp.asarray(d_len)]
        if self.scfg.temperature > 0.0:
            uids = np.zeros((b,), np.int32)
            steps = np.zeros((b,), np.int32)
            for s in act:
                uids[s.index] = s.uid
                steps[s.index] = len(s.emitted)
            args += [jnp.asarray(uids), jnp.asarray(steps)]
        self.obs.add("host", "stage", st0, tick=tick)
        dt0 = self.obs.begin("dispatch")
        emit, n_accept, new_caches = self._spec_jit(*args)
        if self.paged:
            self.kv.adopt(new_caches)
        else:
            self.caches = new_caches
        self.decode_steps += 1
        self.spec_ticks += 1
        self.obs.add("host", "dispatch", dt0, tick=tick)
        ft0 = self.obs.begin("fetch_wait")
        emit = host_fetch(emit)  # (B, k+1) + (B,): the tick's only D2H
        n_accept = host_fetch(n_accept)
        new_tokens = 0
        t1 = time.perf_counter()
        self.obs.add("host", "fetch_wait", ft0, tick=tick)
        et0 = self.obs.begin("emit")
        rt0 = self.obs.begin("spec_rollback") if self.paged else 0
        attrib = (dict(uids=[s.uid for s in act], toks=[])
                  if self.obs.enabled else None)
        for s in act:
            n = int(n_accept[s.index])
            self.spec_accepted += n
            new = emit[s.index, : n + 1].tolist()
            new_tokens += n + 1
            s.cur += n + 1
            s.pending = new[-1]
            s.emitted.extend(new)
            # A spec tick emits n+1 tokens per slot at once: the per-token
            # ITL is the gap since the slot's last emit split across them,
            # observed once per emitted token so the histogram stays
            # token-weighted (same units as a plain tick's single sample).
            gap = (t1 - s.t_last) / (n + 1)
            s.t_last = t1
            if gap > s.itl_max:
                s.itl_max = gap
            for _ in range(n + 1):
                self.metrics.observe("latency.itl_s", gap)
            if attrib is not None:
                attrib["toks"].append(n + 1)
            if self.paged:
                # Rollback: pages faulted for rejected draft positions go
                # home; what stays is exactly pages_for(cur) — the same
                # invariant the plain tick maintains.
                self.kv.truncate(s.index, s.cur)
            self._reap(s)
        if self.paged:
            self.obs.add("transfer", "spec_rollback", rt0,
                         accepted=new_tokens - len(act),
                         **({"uids": attrib["uids"]} if attrib else {}))
        self._account_tick(
            "spec_tick", ot0, t1 - t0, emit_t0=et0,
            n_slots=len(act), new_tokens=new_tokens,
            d2h_bytes=int(emit.nbytes) + int(n_accept.nbytes),
            h2d_bytes=(int(toks.nbytes) + int(cur.nbytes)
                       + int(d_len.nbytes)),
            budget=self._verify_budget, attrib=attrib)

    # -- scheduling loop -------------------------------------------------------

    @tick_path(allowed_fetches=0)
    def step(self) -> None:
        """One scheduling quantum: readmit page-pressure victims, admit
        queued requests into free slots (chunked prefill, interleaved), else
        run one batched decode step.

        Paged backpressure: a request is only popped when the free list can
        hold its prompt; otherwise it waits (FIFO — no overtaking) and the
        active slots keep decoding.  Progress is guaranteed because
        ``submit`` rejects requests that can't finish alone in the pool.
        """
        progressed = False
        if self.paged:
            # Gate on cur + 1, not cur: the very next decode tick writes at
            # position cur, so a page-aligned cur needs one more page than
            # the snapshot covers — gating on cur alone readmits a slot that
            # faults immediately and bounces straight back here.  A
            # registered prefix of the prompt is credited (re-mapped, not
            # allocated), and retained prefix pages are reclaimable, so
            # count both before giving up.  The match -> reclaim ->
            # match-dropped loop converges like the admission gate's.
            while self._preempted and any(s.free for s in self.slots):
                st0 = self.obs.begin("schedule")
                ev0 = self._preempted[0]
                full = self.kv.pages_for(ev0.cur + 1)
                fits = False
                for _ in range(3):
                    shared, _ = self._readmit_prefix(ev0)
                    if full - shared <= self.kv.free_pages:
                        fits = True
                        break
                    if not self.kv.reclaim_for(full - shared):
                        break
                self.obs.add("host", "schedule", st0)
                if not fits:
                    break
                self.readmit(self._preempted.popleft())
                progressed = True
        free = [s for s in self.slots if s.free]
        while self.queue and free:
            req = self.queue[0]
            if self.paged and not self._admission_fits(req):
                break  # backpressure: wait for pages, keep decoding
            self.queue.popleft()
            self._admit(req, free.pop(0))
            progressed = True
        if not progressed:
            self._decode_tick()

    def run(self) -> dict[int, np.ndarray]:
        """Drain the queue and all active slots; returns uid -> tokens for
        the requests finished since the last ``run`` (the outputs buffer is
        handed over, not accumulated across calls)."""
        while self.pending:
            self.step()
        done, self.outputs = self.outputs, {}
        return done

    # -- eviction / readmission ------------------------------------------------

    def evict(self, uid: int) -> EvictedRequest:
        """Pull a request out of its slot (cache rows + positions).

        Paged: the slot's pages are gathered into a b=1 contiguous snapshot
        (page contents travel with the request) and returned to the free
        list — eviction is how page pressure is relieved.
        """
        slot = next((s for s in self.slots if s.uid == uid), None)
        if slot is None:
            raise KeyError(f"uid {uid} not active")
        et0 = self.obs.begin("evict")
        if self.paged:
            caches = self.kv.gather(slot.index, slot.cur)
            n_pages = self.kv.pages_for(slot.cur)
            self.kv.release(slot.index)
        else:
            caches = self._gather_jit(self.caches, jnp.int32(slot.index))
            n_pages = 0
        self.obs.add("transfer", "evict", et0, uid=uid, pages=n_pages,
                     cur=slot.cur, slot=slot.index,
                     state_bytes=self.kv.slot_state_bytes if self.paged else 0)
        ev = EvictedRequest(
            uid=uid, caches=caches,
            cur=slot.cur, pending=slot.pending,
            emitted=list(slot.emitted), max_new=slot.max_new,
            n_pages=n_pages, seq=slot.seq, prompt=slot.prompt,
            ttft_s=slot.ttft_s, t_last=slot.t_last,
            itl_max=slot.itl_max, evictions=slot.evictions + 1)
        slot.uid = None
        slot.emitted = []
        slot.prompt = None
        self._evicted_out += 1
        return ev

    def _readmit_prefix(self, ev: EvictedRequest) -> tuple[int, list[int]]:
        """Registered-prefix match for a readmission -> (n_pages, blocks).

        A preempted sharer used to be re-scattered into exclusive pages —
        duplicating the prefix exactly when the pool is tightest.  With the
        prompt carried on ``EvictedRequest`` the registry lookup can run
        again: matched blocks are byte-verified against the prompt tokens
        and immutable until COW or reclaim, so mapping them at refcount+1
        reproduces the evicted snapshot's prefix rows bitwise."""
        if not (self.paged and self.scfg.prefix_sharing
                and ev.prompt is not None and len(ev.prompt) > 1):
            return 0, []
        chunk = min(self.scfg.prefill_chunk, len(ev.prompt))
        return self.kv.lookup_prefix(
            ev.prompt, min_pages=self.scfg.prefix_min_pages,
            align_tokens=chunk, count=False)

    def readmit(self, ev: EvictedRequest) -> int:
        """Write an evicted request back into any free slot; positions are
        preserved so decode resumes exactly where it stopped.

        With prefix sharing, a registered prefix of the request's prompt is
        re-mapped at refcount+1 (its rows are dropped from the scatter), so
        readmission under pool pressure costs only the unshared tail's
        pages — the ROADMAP's readmission re-map."""
        slot = next((s for s in self.slots if s.free), None)
        if slot is None:
            raise RuntimeError("no free slot to readmit into")
        rt0 = self.obs.begin("readmit")
        shared_pages = 0
        if self.paged:
            shared_pages, blocks = self._readmit_prefix(ev)
            if shared_pages:
                self.kv.map_shared(slot.index, blocks)
            # cur + 1: the next tick writes at position cur, so when cur is
            # page-aligned one more page than the snapshot is needed now —
            # allocating it here instead of faulting next tick keeps a
            # freshly readmitted slot from bouncing straight back out.
            if not self.kv.alloc(slot.index, ev.cur + 1):
                self.kv.release(slot.index)  # drop a mapped prefix cleanly
                raise RuntimeError(
                    f"not enough free pages to readmit uid {ev.uid} "
                    f"(need {self.kv.pages_for(ev.cur + 1)}, "
                    f"free {self.kv.free_pages})")
            self.kv.scatter(slot.index, ev.caches, ev.cur,
                            start_page=shared_pages)
            if shared_pages:
                self.readmit_prefix_hits += 1
                self.readmit_prefix_pages += shared_pages
        else:
            self.caches = self._scatter_jit(
                self.caches, ev.caches, jnp.int32(slot.index))
        self.obs.add("transfer", "readmit", rt0, uid=ev.uid,
                     pages=ev.n_pages, shared_pages=shared_pages,
                     slot=slot.index,
                     state_bytes=self.kv.slot_state_bytes if self.paged else 0)
        slot.uid = ev.uid
        slot.cur = ev.cur
        slot.pending = ev.pending
        slot.emitted = list(ev.emitted)
        slot.max_new = ev.max_new
        slot.prompt = ev.prompt
        slot.ttft_s = ev.ttft_s
        slot.t_last = ev.t_last  # the stall lands in the next tick's gap
        slot.itl_max = ev.itl_max
        slot.evictions = ev.evictions
        # Restore the original admission order: a fresh seq here would make
        # every readmitted request the "youngest" and thus the next victim
        # of _preempt_for_pages — preempt/readmit thrash under pressure.
        slot.seq = ev.seq
        self._evicted_out -= 1
        self.peak_active = max(self.peak_active, len(self.active_slots))
        return slot.index

    # -- policy ----------------------------------------------------------------

    def measure_stage_times(self, prompt_len: int) -> rmetric.StageTimes:
        """Time one prefill chunk and one batched decode step (both warmed)
        on synthetic data; the paper's stage-by-stage methodology (§3.3).

        The paged decode step is donated the pools, so the probe puts every
        row on the trash page at position 0: it writes only there and
        leaves every slot's state rows alone, and its pools are kept. The
        contiguous step's result is discarded.
        """
        chunk = min(self.scfg.prefill_chunk, prompt_len)
        toks = jnp.zeros((1, chunk), jnp.int32)
        caches = self.servable.init_request_cache()
        enc0 = self.servable.probe_enc_out()  # encoder-decoder: the chunk
        # fn cross-attends a (zero) encoder output; None elsewhere
        fn = self.single._prefill_chunk_fn(chunk, True, 0)
        jax.block_until_ready(fn(self.params, caches, toks, enc0, None))
        t0 = time.perf_counter()
        jax.block_until_ready(fn(self.params, caches, toks, enc0, None))
        t_chunk = time.perf_counter() - t0

        b = self.scfg.max_batch

        def decode():
            zeros = jnp.zeros((b,), jnp.int32)
            args = [self.params, jnp.zeros((b, 1), jnp.int32)]
            if self.paged:
                args += [self.kv.pools,
                         jnp.zeros_like(self.kv.device_page_table())]
            else:
                args += [self.caches]
            args += [zeros]
            if self.scfg.temperature > 0.0:
                args += [zeros, zeros]
            nxt, caches = self._decode_step()(*args)
            if self.paged:
                self.kv.adopt(caches)
            jax.block_until_ready(nxt)

        decode()
        t0 = time.perf_counter()
        decode()
        t_decode = time.perf_counter() - t0
        self.last_stage_times = rmetric.StageTimes(h2d=t_chunk, kex=t_decode)
        return self.last_stage_times

    def autotune(self, prompt_len: int) -> ServingPlan:
        """Measure stage times and apply the planned chunk/interleave (and,
        when the paged pool is idle, rebuild it at the planned block size —
        pages in flight *or* outstanding evicted snapshots, whose gathered
        row counts are multiples of the old block size, pin the geometry)."""
        plan = plan_decode_policy(
            self.measure_stage_times(prompt_len), prompt_len=prompt_len,
            max_seq=self.scfg.max_seq)
        self.last_plan = plan  # keep the plan (and its stage times) readable
        chunk_changed = plan.prefill_chunk != self.scfg.prefill_chunk
        self.scfg.prefill_chunk = plan.prefill_chunk
        self.scfg.decode_interleave = plan.decode_interleave
        if chunk_changed and self.paged and self.scfg.prefix_sharing:
            # Registry entries aligned to the old chunk grid can never
            # match a lookup on the new one: drop them now instead of
            # letting them pin pages until pool pressure reclaims them.
            self.kv.clear_stranded_prefixes(self.scfg.prefill_chunk)
        if chunk_changed and self.servable.snapshots is not None:
            # Same staleness for SSM-state snapshots: boundaries sit on
            # the old chunk grid and the lookup only probes the new one.
            self.servable.snapshots.clear()
        if (self.paged and plan.block_size != self.scfg.block_size
                and not self.active_slots and self._evicted_out == 0
                and len(self.kv.registry)):
            # With no slot resident, only the prefix registry is pinning
            # pages (old-geometry prefixes are useless after a rebuild
            # anyway): drop it so the idle pool can adopt the planned size.
            self.kv.clear_prefixes()
        if (self.paged and plan.block_size != self.scfg.block_size
                and self.kv.pages_in_use == 0
                and self._evicted_out == 0
                and not self.queue  # queued requests were validated against
                # the current pool's row capacity
                and self.scfg.max_seq % plan.block_size == 0):
            if self.scfg.num_blocks is not None:
                # Preserve the explicit pool's row budget at the new page
                # granularity (+ the trash page).
                rows = self.kv.allocator.capacity * self.kv.block_size
                self.scfg.num_blocks = rows // plan.block_size + 1
            self.scfg.block_size = plan.block_size
            self.kv = self.servable.make_kv_pool()
            self.kv.metrics = self.metrics
        return plan

    def save_prefixes(self) -> int:
        """Persist the prefix registry to ``scfg.prefix_store`` — the
        other half of the construction-time restore.  Returns entries
        written (0 without a store path or outside paged sharing)."""
        if not (self.paged and self.scfg.prefix_sharing
                and self.scfg.prefix_store):
            return 0
        return self.kv.save_prefixes(self.scfg.prefix_store)
