"""ServableModel: the per-architecture contract behind StreamedBatchEngine.

The paper's generalization story (§4) is that streaming applies per
*dependency category*, not per application.  The serving engine embodies
that: admission, the tick loop, paging, eviction/readmission and
backpressure are category-level mechanics that never mention an
architecture.  Everything architecture-specific — per-slot state layout,
the prefill-chunk step, the decode step, what is shareable and what is
not — lives behind this interface:

  ============  =====================  ===================================
  servable      prefill                decode / sharing
  ============  =====================  ===================================
  transformer   TRUE_DEPENDENT chain   ITERATIVE per-token chain; prefix
                (RAW KV handoff        pages shared COW; speculative
                between chunks)        verify restructures the chain
  mamba         TRUE_DEPENDENT chain   ITERATIVE chain over O(1) state;
                (RAW over the O(1)     sharing degrades to *state
                SSM state)             snapshots* at chunk boundaries
  whisper       SYNC encode staged     ITERATIVE chain; nothing to share
                once per slot, then    (KV depends on each request's
                the chunk chain        encoder output, not on tokens)
  prefix_lm     SYNC image prefix      not served (ServingEngine only)
  ============  =====================  ===================================

Adding an architecture means subclassing :class:`ServableModel`, wiring
its kind into :func:`arch_kind_of` / :func:`build_servable`, and stating
its category mapping in ``tuning.workload.classify_workload`` — the engine
itself does not change.

Import order note: this module imports ``runtime.serving`` eagerly (for
``ServingEngine`` and ``slot_key``); ``StreamedBatchEngine`` imports this
module lazily inside ``__init__`` so the two files never cycle at import
time.
"""

from __future__ import annotations

from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.budget import transfer_budget
from repro.models import transformer as T
from repro.models.transformer import ModelConfig
from repro.runtime import serving
from repro.runtime.kv_cache import PagedKVCache, StateStore

__all__ = [
    "ServableModel", "TransformerServable", "MambaServable",
    "WhisperServable", "arch_kind_of", "build_servable",
]


def arch_kind_of(cfg: ModelConfig) -> str:
    """Serving-arch taxonomy for a ModelConfig.

    ``"whisper"`` = encoder-decoder; ``"prefix_lm"`` = image-prefix VLM
    (paligemma — not streamable-served yet); ``"mamba"`` = any config
    carrying SSM mixers (pure mamba2 and hybrids like jamba — the presence
    of irreversible recurrent state is what changes the serving contract);
    else ``"transformer"``.
    """
    if cfg.is_encoder_decoder:
        return "whisper"
    if cfg.prefix_len > 0:
        return "prefix_lm"
    if any(spec.mixer == "mamba" for spec in cfg.layer_unit):
        return "mamba"
    return "transformer"


def build_servable(
    cfg: ModelConfig, params: Any, scfg: "serving.ServeConfig",
) -> "ServableModel":
    """Factory: the servable for ``cfg``, or a clean rejection.

    Stamps ``scfg.arch_kind`` and re-runs the arch-dependent flag
    validation so a ``ServeConfig`` built before the model was known still
    fails fast (actionable errors, not a crash deep in the tick loop).
    Raises before touching ``params`` so rejection tests can pass stubs.
    """
    kind = arch_kind_of(cfg)
    if kind == "prefix_lm":
        raise NotImplementedError(
            "continuous batching does not serve prefix-LM (image-prefix) "
            "configs: the image prefix is a per-request SYNC stage with no "
            "token key for slot caches; use ServingEngine.generate with "
            "prefix_embeds")
    scfg.arch_kind = kind
    scfg.validate_arch()
    cls = {"transformer": TransformerServable,
           "mamba": MambaServable,
           "whisper": WhisperServable}[kind]
    return cls(cfg, params, scfg)


class ServableModel:
    """Base servable: the decoder-only transformer contract.

    Owns the architecture-specific half of serving; the engine talks to it
    through this surface and never calls ``transformer.decode_step*``
    directly.  The base implementation *is* ``TransformerServable`` —
    subclasses override only what their state layout changes.
    """

    kind = "transformer"

    def __init__(
        self, cfg: ModelConfig, params: Any, scfg: "serving.ServeConfig",
    ):
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        # b=1 chunked-prefill machinery (chunk-fn LRU lives here)
        self.single = serving.ServingEngine(cfg, params, scfg)
        #: StateStore when the arch supports recurrent-state snapshots
        self.snapshots: StateStore | None = None
        #: Expert layers: (2,) int32 on the device, accumulated by the
        #: paged decode program itself: the token-expert pairs it computed
        #: on held experts, and all pairs routed, over its live rows.
        self.device_counters: jax.Array | None = None
        if any(spec.ffn == "moe" for spec in cfg.layer_unit):
            self.reset_device_counters()

    # -- counters kept on the device -------------------------------------------

    def reset_device_counters(self) -> None:
        self.device_counters = jnp.zeros((2,), jnp.int32)

    def read_device_counters(self) -> dict[str, int]:
        """The device counters by name (one small fetch; read at metric
        snapshots, never on the tick path)."""
        here, routed = np.asarray(jax.device_get(self.device_counters))
        return {"moe.decode_pairs_here": int(here),
                "moe.decode_pairs": int(routed)}

    def _counting(self, step):
        """``step`` takes and returns the device counters after the usual
        arguments and outputs; the tick calls it without them, and the
        counters stay on the device."""
        def decode(*args):
            toks, caches, self.device_counters = step(
                *args, self.device_counters)
            return toks, caches

        return decode

    # -- per-slot state layout -------------------------------------------------

    def init_slot_caches(self, bsz: int) -> Any:
        """The batched (contiguous) slot cache: (layers, bsz, max_seq, ...)
        rows plus whatever O(1) per-slot state the arch carries."""
        return T.init_cache(self.cfg, bsz, self.scfg.max_seq, ring=False)

    def init_request_cache(self) -> Any:
        """A b=1 cache shaped like one admission's prefill context (probe
        and measurement use)."""
        return T.init_cache(self.cfg, 1, self.scfg.max_seq, ring=False)

    def make_kv_pool(self) -> PagedKVCache:
        """The paged pool: attention K/V paged, everything else (SSM state,
        cross-attention K/V) slot-indexed opaque state that rides the
        pool's scatter/gather."""
        scfg = self.scfg
        return PagedKVCache(
            self.cfg, max_batch=scfg.max_batch, max_seq=scfg.max_seq,
            block_size=scfg.block_size, num_blocks=scfg.num_blocks,
            jit_cache_cap=scfg.page_jit_cap, kv_dtype=scfg.kv_dtype)

    # -- admission (prefill) ---------------------------------------------------

    def validate_request(
        self, tokens: np.ndarray, enc_inputs: Any,
    ) -> np.ndarray | None:
        """Arch-specific request validation at ``submit`` time; returns the
        normalized ``enc_inputs`` to carry on the Request (None here)."""
        if enc_inputs is not None:
            raise ValueError(
                f"{self.kind!r} servables take no enc_inputs "
                "(encoder-decoder only)")
        return None

    def iter_prefill_chunks(
        self, req: Any, tokens: jax.Array, *, caches: Any = None,
        pos0: int = 0,
    ) -> Iterator[tuple[jax.Array, Any, int]]:
        """The streamed prefill chain for one admission (see
        ``ServingEngine.iter_prefill_chunks`` for the chunk-grid parity
        contract).  ``req`` carries per-request inputs beyond tokens."""
        return self.single.iter_prefill_chunks(
            tokens, caches=caches, pos0=pos0)

    def probe_enc_out(self) -> jax.Array | None:
        """Encoder output stand-in for synthetic stage probes
        (``measure_stage_times``); None for decoder-only archs."""
        return None

    # -- decode ----------------------------------------------------------------

    @transfer_budget(d2h_arrays=1, d2h_outputs=(0,), d2h_bytes_per_slot=4)
    def decode_fn(self, *, paged: bool, donate: bool = True):
        """The jitted batched decode step with on-device sampling fused in.

        Signature matches the engine's tick call: greedy takes
        ``(params, tokens, caches[, page_table], cur_len)``, temperature
        appends ``(uids, steps)`` for the per-slot key fold.

        Transfer budget: the tick fetches output 0 — the (B,) int32 of
        sampled tokens — and nothing else (one int32 per slot per tick).

        With ``donate`` the paged steps are donated the pools (argument 2)
        and update them in place: the caller replaces its pools with the
        step's.
        """
        cfg = self.cfg
        scfg = self.scfg
        temp = float(scfg.temperature)
        pools = 2 if donate else ()

        def _keys(uids, steps):
            return jax.vmap(serving.slot_key)(uids, steps)

        if paged and self.device_counters is not None:
            kern = scfg.paged_kernel
            per_row = cfg.top_k * cfg.n_repeats * sum(
                spec.ffn == "moe" for spec in cfg.layer_unit)

            def tally(out, cur, acc):
                toks, caches, here = out
                live = cur > 0  # free slots decode at position 0
                return toks, caches, acc + jnp.stack([
                    jnp.sum(jnp.where(live, here, 0)),
                    jnp.sum(live.astype(jnp.int32)) * per_row])

            if temp > 0.0:
                return self._counting(jax.jit(
                    lambda p, t, c, pt, l, u, s, a: tally(
                        T.decode_and_sample_paged(
                            cfg, p, t, c, pt, l, temperature=temp,
                            key=_keys(u, s), paged_kernel=kern,
                            count_pairs=True), l, a), donate_argnums=pools))
            return self._counting(jax.jit(
                lambda p, t, c, pt, l, a: tally(T.decode_and_sample_paged(
                    cfg, p, t, c, pt, l, paged_kernel=kern,
                    count_pairs=True), l, a), donate_argnums=pools))
        if paged:
            kern = scfg.paged_kernel
            if temp > 0.0:
                return jax.jit(
                    lambda p, t, c, pt, l, u, s: T.decode_and_sample_paged(
                        cfg, p, t, c, pt, l, temperature=temp,
                        key=_keys(u, s), paged_kernel=kern),
                    donate_argnums=pools)
            return jax.jit(
                lambda p, t, c, pt, l: T.decode_and_sample_paged(
                    cfg, p, t, c, pt, l, paged_kernel=kern),
                donate_argnums=pools)
        if temp > 0.0:
            return jax.jit(
                lambda p, t, c, l, u, s: T.decode_and_sample(
                    cfg, p, t, c, l, temperature=temp, key=_keys(u, s)))
        return jax.jit(
            lambda p, t, c, l: T.decode_and_sample(cfg, p, t, c, l))

    def make_verifier(self, *, paged: bool):
        """Jitted speculative verify step (spec decode restructures the
        ITERATIVE chain into verify chunks).  Only the transformer carries
        rollback-safe state; ``ServeConfig.validate_arch`` rejects
        ``spec_decode`` before this is ever reached elsewhere."""
        raise NotImplementedError(
            f"speculative decode is not available for {self.kind!r} "
            "servables")

    # -- recurrent-state snapshots (mamba; no-ops elsewhere) -------------------

    def lookup_snapshot(self, tokens: np.ndarray) -> tuple[int, Any]:
        """Longest stored chunk-aligned proper-prefix state snapshot of
        ``tokens`` -> (n_tokens, device caches); (0, None) on miss."""
        return 0, None

    def maybe_snapshot(
        self, tokens: np.ndarray, caches: Any, pos: int,
    ) -> None:
        """Offer the prefill state at absolute position ``pos`` for
        snapshotting (called once per dispatched chunk)."""


class TransformerServable(ServableModel):
    """Decoder-only transformer: the base contract plus speculative decode
    (KV writes mask/roll back, so verify-and-truncate is safe)."""

    kind = "transformer"

    # A spec tick fetches (emit, n_accept): (B, k+1) + (B,) int32 —
    # 4 * (spec_k + 2) bytes per slot, still O(tokens) not O(vocab).
    @transfer_budget(
        d2h_arrays=2, d2h_outputs=(0, 1),
        d2h_bytes_per_slot=lambda scfg: 4 * (scfg.spec_k + 2))
    def make_verifier(self, *, paged: bool):
        from repro.runtime import spec as _spec
        return _spec.make_verifier(
            self.cfg, paged=paged,
            temperature=float(self.scfg.temperature),
            paged_kernel=self.scfg.paged_kernel)


class MambaServable(ServableModel):
    """SSM (mamba2) and hybrid (jamba, granite) configs.

    Per-slot state is O(1) recurrent (SSM state + conv tail), carried by
    the cache/pool scatter-gather as opaque per-slot leaves — eviction,
    readmission and preemption work unchanged. With fused prefill (the
    paged default) each chunk reads and writes the admitted slot's state
    rows in the pool directly.  Page-granular prefix
    sharing is impossible (the state at position ``t`` summarizes *all*
    of ``[0, t)``), so sharing degrades to **state snapshots**: admission
    restores the longest stored chunk-aligned proper prefix of the prompt
    and streams only the uncovered tail.  Boundaries sit on the prefill
    chunk grid, so a resumed prefill dispatches the exact chunk tasks a
    full prefill would — token parity is bitwise (the page path's
    argument, transplanted to state).
    """

    kind = "mamba"

    def __init__(
        self, cfg: ModelConfig, params: Any, scfg: "serving.ServeConfig",
    ):
        super().__init__(cfg, params, scfg)
        if scfg.state_snapshots:
            if any(spec.mixer != "mamba" for spec in cfg.layer_unit):
                raise NotImplementedError(
                    "state_snapshots reuse O(1) recurrent state; hybrid "
                    "configs (jamba) also carry attention KV whose "
                    "snapshot would be O(max_seq) per entry — serve "
                    "hybrids without state_snapshots")
            self.snapshots = StateStore()

    def lookup_snapshot(self, tokens: np.ndarray) -> tuple[int, Any]:
        if self.snapshots is None:
            return 0, None
        n, snap = self.snapshots.lookup(
            np.asarray(tokens, np.int32),
            align_tokens=self.scfg.prefill_chunk)
        if not n:
            return 0, None
        return n, jax.tree.map(jnp.asarray, snap)

    def maybe_snapshot(
        self, tokens: np.ndarray, caches: Any, pos: int,
    ) -> None:
        if self.snapshots is None or caches is None:
            return
        # Proper chunk-aligned prefixes only: a full-prompt "prefix" can
        # never be looked up (admission needs >= 1 tail token), and an
        # unaligned one would break the chunk-grid parity argument.
        if 0 < pos < len(tokens) and pos % self.scfg.prefill_chunk == 0:
            self.snapshots.put(
                np.asarray(tokens[:pos], np.int32),
                jax.tree.map(np.asarray, caches))


class WhisperServable(ServableModel):
    """Encoder-decoder (whisper): the encoded audio prefix is the paper's
    SYNC transfer — staged once per slot at admission, before the decode
    stream begins — and decode is the usual ITERATIVE chain with
    cross-attention reading the slot's fixed-size encoder K/V.

    Cross-attention K/V is per-slot opaque state (fixed ``encoder_seq``
    rows, prefill-computed), so evict/readmit carry it automatically.
    Prefix sharing is rejected (``validate_arch``): the registry keys
    pages by prompt *tokens*, but whisper's self-attention KV depends on
    each request's encoder output — identical text prefixes are not
    shareable across requests.
    """

    kind = "whisper"

    def validate_request(
        self, tokens: np.ndarray, enc_inputs: Any,
    ) -> np.ndarray:
        cfg = self.cfg
        if enc_inputs is None:
            raise ValueError(
                "whisper serving needs enc_inputs per request: the "
                f"encoded audio frames, shape (encoder_seq="
                f"{cfg.encoder_seq}, d_model={cfg.d_model})")
        enc = np.asarray(enc_inputs)
        if enc.ndim == 2:
            enc = enc[None]
        if enc.shape != (1, cfg.encoder_seq, cfg.d_model):
            raise ValueError(
                f"enc_inputs must be (encoder_seq={cfg.encoder_seq}, "
                f"d_model={cfg.d_model}); got "
                f"{tuple(np.asarray(enc_inputs).shape)} (the slot's "
                "cross-attention K/V is sized for the full encoder_seq)")
        return enc

    def iter_prefill_chunks(
        self, req: Any, tokens: jax.Array, *, caches: Any = None,
        pos0: int = 0,
    ) -> Iterator[tuple[jax.Array, Any, int]]:
        # No sharing/snapshots for whisper: every admission starts at 0
        # with its own SYNC encode.
        assert caches is None and pos0 == 0, \
            "whisper admissions never resume a shared prefix"
        return self.single.iter_prefill_chunks(
            tokens, enc_inputs=jnp.asarray(req.enc_inputs))

    def probe_enc_out(self) -> jax.Array:
        cfg = self.cfg
        return jnp.zeros(
            (1, cfg.encoder_seq, cfg.d_model), cfg.compute_dtype)
