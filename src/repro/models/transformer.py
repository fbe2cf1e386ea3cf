"""Composable multi-architecture LM backbone.

One config system covers all 10 assigned architectures: dense GQA
transformers (internlm2, phi4, qwen3), gemma2 (alternating local/global,
softcaps, sandwich norms), MoE (mixtral, qwen2-moe), Mamba2 (SSM), Jamba
(hybrid mamba/attention + MoE), Whisper (encoder-decoder, stub audio
frontend) and PaliGemma (prefix-LM VLM, stub vision frontend).

Layers are described by a repeating ``layer_unit`` (a tuple of LayerSpec);
parameters of each unit are stacked over the repeat axis and executed with
``lax.scan`` (keeps HLO size O(1) in depth; remat applies per repeat).

Streaming (the paper's technique) appears here as:
  * chunked flash attention (repro.models.attention) -- block-pair streams;
  * chunked CE loss (repro.models.layers) -- Independent-task streams;
  * chunked MoE dispatch (repro.models.moe) -- a2a/compute pipelining;
  * chunked SSD scan (repro.models.mamba) -- True-dependent state handoff;
  * chunked prefill (repro.runtime.serving) -- built on ``prefill`` here.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import quant
from repro.models import attention as attn_lib
from repro.models import layers, mamba, meshutil, moe

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: str = "attn"  # "attn" | "attn_local" | "mamba" | "none"
    ffn: str = "dense"  # "dense" | "moe" | "none"
    cross_attn: bool = False


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 128
    layer_unit: tuple[LayerSpec, ...] = (LayerSpec(),)

    # attention
    use_rope: bool = True
    rope_theta: float = 1e4
    qk_norm: bool = False
    attn_softcap: float = 0.0
    final_softcap: float = 0.0
    sliding_window: int = 0  # window for "attn_local" mixers (and mixtral SWA)
    query_scale: float | None = None  # None -> 1/sqrt(head_dim)
    sandwich_norm: bool = False  # gemma2 post-attn/post-ffn norms
    sinusoidal_pos: bool = False  # whisper-style absolute positions

    # ffn
    ffn_kind: str = "swiglu"  # "swiglu" | "geglu" | "gelu_mlp"

    # moe
    n_experts: int = 0
    top_k: int = 2
    expert_d_ff: int | None = None
    n_shared_experts: int = 0
    shared_d_ff: int | None = None
    capacity_factor: float = 1.25
    moe_chunk: int = 1024
    router_aux_coef: float = 0.01
    moe_impl: str = "gather"  # "gather" (optimized) | "einsum" (baseline)
    expert_shards: int = 1  # virtual-expert TP folded into EP
    n_experts_pad: int | None = None  # dead expert slots for EP divisibility
    shared_gate: bool = True  # qwen2-moe's sigmoid gate on the shared MLP
    # Expert parallelism's share of a layer: the router scores all
    # n_experts, this layer holds experts [0, experts_held) and computes
    # their part of the result (0 = all held).
    experts_held: int = 0

    # mamba
    ssm_state: int = 128
    mamba_headdim: int = 64
    mamba_expand: int = 2
    ssd_chunk: int = 64
    ssm_cache_dtype: Any = jnp.float32  # per-slot SSM state as stored; the
    # update itself always runs in float32

    # encoder-decoder (whisper)
    n_encoder_layers: int = 0
    encoder_seq: int = 0  # stub frontend: frames arrive pre-embedded

    # vlm (paligemma)
    prefix_len: int = 0  # image patch embeddings prepended to text

    # embeddings / head
    tie_embeddings: bool = False
    embed_scale: bool = False  # gemma: h *= sqrt(d_model)
    vocab_pad_to: int = 256
    # granite: h = embed_multiplier * E[tok]; each sublayer's output enters
    # the residual times residual_multiplier; logits are divided by
    # logits_scaling
    embed_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    norm_eps: float = 1e-6  # every RMSNorm of the model

    # compute
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16
    attn_chunk: int = 512
    loss_chunk: int = 512
    remat: str = "dots"  # "none" | "dots" | "full"
    scan_layers: bool = True

    @property
    def padded_vocab(self) -> int:
        v, m = self.vocab_size, self.vocab_pad_to
        return ((v + m - 1) // m) * m

    @property
    def n_repeats(self) -> int:
        assert self.n_layers % len(self.layer_unit) == 0, (
            self.n_layers, len(self.layer_unit))
        return self.n_layers // len(self.layer_unit)

    @property
    def is_encoder_decoder(self) -> bool:
        return self.n_encoder_layers > 0

    def spec_window(self, spec: LayerSpec) -> int:
        return self.sliding_window if spec.mixer == "attn_local" else (
            self.sliding_window if self.sliding_window and all(
                s.mixer != "attn_local" for s in self.layer_unit) else 0)

    def param_count(self) -> int:
        """Total parameter count (exact, from shapes)."""
        shapes = jax.eval_shape(lambda k: init_params(self, k), jax.random.PRNGKey(0))
        return sum(int(math.prod(x.shape)) for x in jax.tree.leaves(shapes))

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: top_k + shared experts only;
        dead padding experts are never touched)."""
        total = self.param_count()
        if self.n_experts == 0:
            return total
        e_ff = self.expert_d_ff or self.d_ff
        per_expert = 3 * self.d_model * e_ff
        n_moe_layers = sum(
            1 for s in self.layer_unit if s.ffn == "moe") * self.n_repeats
        stored = self.n_experts_pad or self.n_experts
        inactive = n_moe_layers * (stored - self.top_k) * per_expert
        return total - inactive


# ----------------------------------------------------------------------------
# Init
# ----------------------------------------------------------------------------


def _layer_init(cfg: ModelConfig, spec: LayerSpec, key) -> Params:
    ks = jax.random.split(key, 8)
    p: Params = {}
    dt = cfg.param_dtype
    if spec.mixer in ("attn", "attn_local"):
        p["mixer_norm"] = layers.rmsnorm_init(cfg.d_model, dt)
        p["mixer"] = attn_lib.attention_init(
            ks[0], d_model=cfg.d_model, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, dtype=dt,
            qk_norm=cfg.qk_norm)
        if cfg.sandwich_norm:
            p["post_mixer_norm"] = layers.rmsnorm_init(cfg.d_model, dt)
    elif spec.mixer == "mamba":
        p["mixer_norm"] = layers.rmsnorm_init(cfg.d_model, dt)
        p["mixer"] = mamba.mamba_init(
            ks[0], d_model=cfg.d_model, expand=cfg.mamba_expand,
            headdim=cfg.mamba_headdim, d_state=cfg.ssm_state, dtype=dt)
    if spec.cross_attn:
        p["cross_norm"] = layers.rmsnorm_init(cfg.d_model, dt)
        p["cross"] = attn_lib.attention_init(
            ks[1], d_model=cfg.d_model, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, dtype=dt)
    if spec.ffn == "dense":
        p["ffn_norm"] = layers.rmsnorm_init(cfg.d_model, dt)
        p["ffn"] = layers.ffn_init(ks[2], cfg.d_model, cfg.d_ff, dt, kind=cfg.ffn_kind)
        if cfg.sandwich_norm:
            p["post_ffn_norm"] = layers.rmsnorm_init(cfg.d_model, dt)
    elif spec.ffn == "moe":
        p["ffn_norm"] = layers.rmsnorm_init(cfg.d_model, dt)
        p["ffn"] = moe.moe_init(
            ks[2], d_model=cfg.d_model, d_ff=cfg.expert_d_ff or cfg.d_ff,
            n_experts=cfg.n_experts, n_shared_experts=cfg.n_shared_experts,
            shared_d_ff=cfg.shared_d_ff, dtype=dt,
            expert_shards=cfg.expert_shards, n_experts_pad=cfg.n_experts_pad,
            n_held=cfg.experts_held, shared_gate=cfg.shared_gate)
    return p


def _block_init(cfg: ModelConfig, key, *, unit=None) -> Params:
    unit = unit if unit is not None else cfg.layer_unit
    ks = jax.random.split(key, len(unit))
    return {f"layer{i}": _layer_init(cfg, spec, ks[i]) for i, spec in enumerate(unit)}


_ENC_UNIT = (LayerSpec(mixer="attn", ffn="dense"),)


def init_params(cfg: ModelConfig, key) -> Params:
    keys = jax.random.split(key, 6)
    v = cfg.padded_vocab
    p: Params = {
        "embed": layers.embed_init(keys[0], (v, cfg.d_model), cfg.param_dtype),
        "final_norm": layers.rmsnorm_init(cfg.d_model, cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = layers.embed_init(keys[1], (v, cfg.d_model), cfg.param_dtype)

    block_keys = jax.random.split(keys[2], cfg.n_repeats)
    p["blocks"] = jax.vmap(lambda k: _block_init(cfg, k))(block_keys)

    if cfg.is_encoder_decoder:
        enc_keys = jax.random.split(keys[3], cfg.n_encoder_layers)
        enc_cfg = dataclasses.replace(cfg, ffn_kind="gelu_mlp")
        p["encoder"] = {
            "blocks": jax.vmap(lambda k: _block_init(enc_cfg, k, unit=_ENC_UNIT))(enc_keys),
            "final_norm": layers.rmsnorm_init(cfg.d_model, cfg.param_dtype),
        }
    return p


# ----------------------------------------------------------------------------
# Caches
# ----------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, bsz: int, max_seq: int, *, enc_seq: int | None = None,
               ring: bool = True) -> Params:
    """Decode caches, stacked over the repeat axis per unit position.

    ``ring=True`` bounds SWA layers' caches at the window size (ring
    buffer -- memory-optimal decode); ``ring=False`` allocates full-length
    caches (required by the streamed-prefill continuation path).
    """
    r = cfg.n_repeats
    dt = cfg.compute_dtype
    cache: Params = {"blocks": {}}
    for i, spec in enumerate(cfg.layer_unit):
        c: Params = {}
        if spec.mixer in ("attn", "attn_local"):
            window = cfg.sliding_window if (
                spec.mixer == "attn_local" or (
                    cfg.sliding_window > 0 and all(s.mixer != "attn_local" for s in cfg.layer_unit)
                )
            ) else 0
            s_cache = min(window, max_seq) if (window > 0 and ring) else max_seq
            shape = (r, bsz, s_cache, cfg.n_kv_heads, cfg.head_dim)
            c["k"] = jnp.zeros(shape, dt)
            c["v"] = jnp.zeros(shape, dt)
        elif spec.mixer == "mamba":
            d_inner, n_heads, conv_dim = mamba.mamba_dims(
                cfg.d_model, expand=cfg.mamba_expand, headdim=cfg.mamba_headdim,
                d_state=cfg.ssm_state)
            c["ssm"] = jnp.zeros((r, bsz, n_heads, cfg.mamba_headdim, cfg.ssm_state),
                                 cfg.ssm_cache_dtype)
            c["conv"] = jnp.zeros((r, bsz, mamba.CONV_WIDTH - 1, conv_dim), dt)
        if spec.cross_attn:
            es = enc_seq or cfg.encoder_seq
            shape = (r, bsz, es, cfg.n_kv_heads, cfg.head_dim)
            c["cross_k"] = jnp.zeros(shape, dt)
            c["cross_v"] = jnp.zeros(shape, dt)
        cache["blocks"][f"layer{i}"] = c
    return cache


#: Paged-cache leaves shared by every slot through the page table; any other
#: leaf (SSM and conv state, cross-attention K/V) has one row per slot.
PAGED_LEAVES = ("k", "v", "k_scale", "v_scale")


def init_paged_cache(
    cfg: ModelConfig, bsz: int, num_blocks: int, block_size: int,
    kv_dtype: str = "fp32",
) -> Params:
    """Paged decode caches: one global page pool per attention unit position.

    Attention K/V leaves are (r, num_blocks, block_size, n_kv_heads, head_dim)
    — a pool of fixed-size pages shared by all ``bsz`` slots and indexed
    through a per-slot page table (see ``repro.runtime.kv_cache``).  Block 0
    is conventionally the trash page (free slots' padding writes land there).
    Mamba SSM/conv states are O(1) per slot and stay slot-indexed, exactly as
    in :func:`init_cache`; so do encoder-decoder cross-attention K/V, which
    are fixed-size (encoder_seq) per slot and prefill-computed — nothing to
    page, everything to evict/readmit as opaque per-slot state.

    ``kv_dtype`` other than "fp32" stores quantized pages (int8/fp8) plus
    per-page per-kv-head f32 scale leaves ``k_scale``/``v_scale`` of shape
    (r, num_blocks, n_kv_heads); see ``repro.kernels.quant``.
    """
    r = cfg.n_repeats
    dt = cfg.compute_dtype
    quantized = quant.is_quantized(kv_dtype)
    pool_dt = quant.storage_dtype(kv_dtype) if quantized else dt
    cache: Params = {"blocks": {}}
    for i, spec in enumerate(cfg.layer_unit):
        c: Params = {}
        if spec.mixer in ("attn", "attn_local"):
            shape = (r, num_blocks, block_size, cfg.n_kv_heads, cfg.head_dim)
            c["k"] = jnp.zeros(shape, pool_dt)
            c["v"] = jnp.zeros(shape, pool_dt)
            if quantized:
                sshape = (r, num_blocks, cfg.n_kv_heads)
                c["k_scale"] = jnp.zeros(sshape, jnp.float32)
                c["v_scale"] = jnp.zeros(sshape, jnp.float32)
        elif spec.mixer == "mamba":
            d_inner, n_heads, conv_dim = mamba.mamba_dims(
                cfg.d_model, expand=cfg.mamba_expand, headdim=cfg.mamba_headdim,
                d_state=cfg.ssm_state)
            c["ssm"] = jnp.zeros(
                (r, bsz, n_heads, cfg.mamba_headdim, cfg.ssm_state),
                cfg.ssm_cache_dtype)
            c["conv"] = jnp.zeros((r, bsz, mamba.CONV_WIDTH - 1, conv_dim), dt)
        if spec.cross_attn:
            shape = (r, bsz, cfg.encoder_seq, cfg.n_kv_heads, cfg.head_dim)
            c["cross_k"] = jnp.zeros(shape, dt)
            c["cross_v"] = jnp.zeros(shape, dt)
        cache["blocks"][f"layer{i}"] = c
    return cache


# ----------------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------------


def _apply_layer(
    cfg: ModelConfig,
    spec: LayerSpec,
    p: Params,
    h: jax.Array,
    *,
    positions: jax.Array | None,
    cache: Params | None,
    cur_len: jax.Array | None,
    enc_out: jax.Array | None,
    prefix_len: int,
    causal: bool,
    q_offset: int = 0,
    page_table: jax.Array | None = None,
    paged_kernel: bool = False,
    layer: jax.Array | None = None,
) -> tuple[jax.Array, Params, jax.Array, jax.Array | None]:
    """One layer. Returns (h, new_cache, aux_loss, held pairs): the last is
    the (B, S) count of each token's routed experts that this layer holds
    (None without a served expert layer). With ``layer``, the cache's page
    pools are the stacked ones and ``layer`` is this layer's index in them
    (see :func:`forward_hidden`)."""
    aux = jnp.float32(0.0)
    held = None
    new_cache: Params = dict(cache) if cache is not None else None

    def norm(name, x):
        return layers.rmsnorm(p[name], x, eps=cfg.norm_eps)

    def residual(resid, out):
        if cfg.residual_multiplier != 1.0:
            out = out * jnp.asarray(cfg.residual_multiplier, out.dtype)
        return resid + out

    if spec.mixer in ("attn", "attn_local"):
        window = cfg.sliding_window if spec.mixer == "attn_local" else (
            cfg.sliding_window if all(s.mixer != "attn_local" for s in cfg.layer_unit) else 0)
        resid = h
        x = norm("mixer_norm", h)
        kv_cache = None
        if cache is not None and "k" in cache:
            kv_cache = {key: cache[key]
                        for key in ("k", "v", "k_scale", "v_scale")
                        if key in cache}
        out, upd = attn_lib.attention_apply(
            p["mixer"], x,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
            positions=positions if cfg.use_rope else None,
            rope_theta=cfg.rope_theta, causal=causal, window=window,
            prefix_len=prefix_len, softcap_val=cfg.attn_softcap,
            scale=cfg.query_scale, chunk=cfg.attn_chunk, qk_norm=cfg.qk_norm,
            cache=kv_cache, cur_len=cur_len, q_offset=q_offset,
            page_table=page_table, paged_kernel=paged_kernel,
            norm_eps=cfg.norm_eps, layer=layer)
        if cfg.sandwich_norm:
            out = norm("post_mixer_norm", out)
        h = residual(resid, out)
        if upd is not None and new_cache is not None:
            for key in ("k", "v", "k_scale", "v_scale"):
                if key in upd:
                    new_cache[key] = upd[key]
    elif spec.mixer == "mamba":
        resid = h
        x = norm("mixer_norm", h)
        decode = cur_len is not None
        out, upd = mamba.mamba_apply(
            p["mixer"], x, headdim=cfg.mamba_headdim, d_state=cfg.ssm_state,
            expand=cfg.mamba_expand, chunk=cfg.ssd_chunk,
            state=cache["ssm"] if (cache is not None and "ssm" in cache) else None,
            conv_state=cache["conv"] if (cache is not None and "conv" in cache) else None,
            decode=decode, norm_eps=cfg.norm_eps)
        h = residual(resid, out)
        if new_cache is not None:
            for key in ("ssm", "conv"):
                new = upd[key].astype(cache[key].dtype)
                if decode and jnp.ndim(cur_len) == 1:
                    # Continuous batching: a free slot (cur 0) keeps its
                    # state rows, which an admission in flight may be
                    # filling chunk by chunk through the pool.
                    live = (cur_len > 0).reshape((-1,) + (1,) * (new.ndim - 1))
                    new = jnp.where(live, new, cache[key])
                new_cache[key] = new

    if spec.cross_attn:
        resid = h
        x = norm("cross_norm", h)
        b, s, _ = x.shape
        q = (x @ p["cross"]["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
        if cur_len is not None and cache is not None and "cross_k" in cache:
            kc, vc = cache["cross_k"], cache["cross_v"]
            out = attn_lib.decode_attention(
                q, kc, vc, cur_len=jnp.int32(kc.shape[1] - 1))
        else:
            assert enc_out is not None
            kc = (enc_out @ p["cross"]["wk"]).reshape(
                b, enc_out.shape[1], cfg.n_kv_heads, cfg.head_dim)
            vc = (enc_out @ p["cross"]["wv"]).reshape(
                b, enc_out.shape[1], cfg.n_kv_heads, cfg.head_dim)
            out = attn_lib.flash_attention_ref(
                q, kc, vc, chunk=cfg.attn_chunk, causal=False)
            if new_cache is not None and "cross_k" in (cache or {}):
                new_cache["cross_k"] = kc.astype(cache["cross_k"].dtype)
                new_cache["cross_v"] = vc.astype(cache["cross_v"].dtype)
        out = out.reshape(b, s, cfg.n_heads * cfg.head_dim) @ p["cross"]["wo"]
        h = residual(resid, out)

    if spec.ffn == "dense":
        resid = h
        x = norm("ffn_norm", h)
        out = layers.ffn_apply(p["ffn"], x, kind=cfg.ffn_kind)
        if cfg.sandwich_norm:
            out = norm("post_ffn_norm", out)
        h = residual(resid, out)
    elif spec.ffn == "moe":
        resid = h
        x = norm("ffn_norm", h)
        if cache is not None or cfg.experts_held:
            # Serving (and any expert-parallel share): dropless, so that a
            # token's output never depends on its batch neighbours.
            out, held = moe.moe_apply_held(
                p["ffn"], x, top_k=cfg.top_k,
                expert_shards=cfg.expert_shards)
        else:
            out, aux = moe.moe_apply(
                p["ffn"], x, top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor,
                moe_chunk=cfg.moe_chunk, impl=cfg.moe_impl,
                expert_shards=cfg.expert_shards)
        h = residual(resid, out)

    return h, (new_cache if new_cache is not None else {}), aux, held


def _remat_wrap(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn, policy=None)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


def forward_hidden(
    cfg: ModelConfig,
    params: Params,
    h: jax.Array,  # (B, S, D) embedded inputs
    *,
    positions: jax.Array | None,
    caches: Params | None = None,  # stacked over repeats
    cur_len: jax.Array | None = None,
    enc_out: jax.Array | None = None,
    prefix_len: int = 0,
    causal: bool = True,
    unit: tuple[LayerSpec, ...] | None = None,
    blocks: Params | None = None,
    q_offset: int = 0,
    page_table: jax.Array | None = None,
    paged_kernel: bool = False,
    count_pairs: bool = False,
) -> tuple[jax.Array, Params | None, jax.Array]:
    """Run the stacked blocks. Returns (h, new caches, aux loss), and with
    ``count_pairs`` a fourth item: the (B, S) count, over every layer, of
    each token's routed experts that the layers hold.

    ``page_table`` switches attention layers to the paged KV pool (cache
    leaves are then (r, num_blocks, block_size, hkv, hd)); the table itself
    is shared by every layer, only the pools are per layer. The paged
    caches are then *carried* through the layer loop rather than scanned
    over: each layer writes its new rows into the stacked pools at its
    index and reads its pages back through the table (or the kernel's
    DMAs), and a per-slot leaf's layer is read and written back in place,
    so a program that is donated the caches updates them where they lie
    instead of slicing every layer out and stacking a fresh copy.
    Contiguous caches keep the scanned form: their attention reads a
    layer's whole cache.
    """
    unit = unit if unit is not None else cfg.layer_unit
    blocks = blocks if blocks is not None else params["blocks"]
    block_caches = caches["blocks"] if caches is not None else None
    carried = block_caches is not None and page_table is not None

    def run_layers(h, aux, pairs, bp, bc, layer):
        new_bc = {}
        for i, spec in enumerate(unit):
            lc = bc.get(f"layer{i}") if bc is not None else None
            if carried:
                # Per-slot leaves: this layer's rows; page pools: whole.
                lc = {k: v if k in PAGED_LEAVES
                      else jax.lax.dynamic_index_in_dim(v, layer, 0, False)
                      for k, v in lc.items()}
            h, nc, a, held = _apply_layer(
                cfg, spec, bp[f"layer{i}"], h,
                positions=positions, cache=lc, cur_len=cur_len,
                enc_out=enc_out, prefix_len=prefix_len, causal=causal,
                q_offset=q_offset, page_table=page_table,
                paged_kernel=paged_kernel, layer=layer)
            # Pin activations to batch-sharded layout at layer boundaries so
            # the embedding table's sharding can't flip the whole stack to a
            # replicated-batch TP layout through the scan carry.
            h = meshutil.shard_batch(h)
            if carried:
                # A per-slot leaf the layer left alone (cross-attention K/V
                # in decode) is carried on untouched.
                stacked = bc[f"layer{i}"]
                nc = {k: v if k in PAGED_LEAVES
                      else stacked[k] if v is lc[k]
                      else jax.lax.dynamic_update_index_in_dim(
                          stacked[k], v.astype(stacked[k].dtype), layer, 0)
                      for k, v in nc.items()}
            new_bc[f"layer{i}"] = nc
            aux = aux + a
            if pairs is not None and held is not None:
                pairs = pairs + held
        return h, aux, pairs, new_bc

    pairs0 = jnp.zeros(h.shape[:2], jnp.int32) if count_pairs else None
    if carried:
        def body(carry, xs):
            h, aux, pairs, bc = carry
            bp, layer = xs
            return run_layers(h, aux, pairs, bp, bc, layer), None

        r = jax.tree.leaves(blocks)[0].shape[0]
        (h, aux, pairs, new_caches), _ = jax.lax.scan(
            _remat_wrap(cfg, body),
            (h, jnp.float32(0.0), pairs0, block_caches),
            (blocks, jnp.arange(r)))
    else:
        def body(carry, xs):
            h, aux, pairs = carry
            bp, bc = xs
            h, aux, pairs, new_bc = run_layers(h, aux, pairs, bp, bc, None)
            return (h, aux, pairs), new_bc

        (h, aux, pairs), new_caches = jax.lax.scan(
            _remat_wrap(cfg, body), (h, jnp.float32(0.0), pairs0),
            (blocks, block_caches))
    out_caches = {"blocks": new_caches} if caches is not None else None
    if count_pairs:
        return h, out_caches, aux, pairs
    return h, out_caches, aux


def _embed_tokens(cfg: ModelConfig, params: Params, tokens: jax.Array) -> jax.Array:
    h = params["embed"][tokens].astype(cfg.compute_dtype)
    if cfg.embed_scale:
        h = h * jnp.asarray(math.sqrt(cfg.d_model), cfg.compute_dtype)
    if cfg.embed_multiplier != 1.0:
        h = h * jnp.asarray(cfg.embed_multiplier, cfg.compute_dtype)
    return h


def _unembed(cfg: ModelConfig, params: Params) -> jax.Array:
    return params["embed"] if cfg.tie_embeddings else params["unembed"]


def final_norm(cfg: ModelConfig, params: Params, h: jax.Array) -> jax.Array:
    return layers.rmsnorm(params["final_norm"], h, eps=cfg.norm_eps)


def head_logits(cfg: ModelConfig, params: Params, h: jax.Array) -> jax.Array:
    """float32 logits of final hidden states ``h`` (before the final norm):
    norm, output head, ``logits_scaling`` and the final softcap."""
    h = final_norm(cfg, params, h)
    logits = h.astype(jnp.float32) @ _unembed(cfg, params).astype(jnp.float32).T
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return layers.softcap(logits, cfg.final_softcap)


def encode(cfg: ModelConfig, params: Params, enc_inputs: jax.Array) -> jax.Array:
    """Whisper encoder over stub frame embeddings (B, enc_seq, D)."""
    h = enc_inputs.astype(cfg.compute_dtype)
    s = h.shape[1]
    h = h + layers.sinusoidal_positions(s, cfg.d_model, cfg.compute_dtype)[None]
    h = meshutil.shard_batch(h)
    enc_cfg = dataclasses.replace(cfg, ffn_kind="gelu_mlp", use_rope=False)
    h, _, _ = forward_hidden(
        enc_cfg, params, h, positions=None, causal=False,
        unit=_ENC_UNIT, blocks=params["encoder"]["blocks"])
    return layers.rmsnorm(params["encoder"]["final_norm"], h, eps=cfg.norm_eps)


def _prepare_inputs(
    cfg: ModelConfig, params: Params, batch: dict[str, jax.Array]
) -> tuple[jax.Array, jax.Array | None, jax.Array, int]:
    """Embed tokens (+ prefix / encoder). Returns (h, enc_out, positions, prefix_len)."""
    tokens = batch["tokens"]
    h = _embed_tokens(cfg, params, tokens)
    enc_out = None
    prefix_len = 0
    if cfg.is_encoder_decoder:
        enc_out = encode(cfg, params, batch["enc_inputs"])
    if cfg.prefix_len > 0 and "prefix_embeds" in batch:
        pre = batch["prefix_embeds"].astype(cfg.compute_dtype)
        if cfg.embed_scale:
            pre = pre * jnp.asarray(math.sqrt(cfg.d_model), cfg.compute_dtype)
        h = jnp.concatenate([pre, h], axis=1)
        prefix_len = pre.shape[1]
    s = h.shape[1]
    if cfg.sinusoidal_pos:
        h = h + layers.sinusoidal_positions(s, cfg.d_model, cfg.compute_dtype)[None]
    h = meshutil.shard_batch(h)
    positions = jnp.arange(s)
    return h, enc_out, positions, prefix_len


def train_loss(
    cfg: ModelConfig, params: Params, batch: dict[str, jax.Array]
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Next-token CE (+ MoE aux). batch: tokens (B,S) [+ enc_inputs / prefix_embeds / loss_mask]."""
    tokens = batch["tokens"]
    b, s_tok = tokens.shape
    h, enc_out, positions, prefix_len = _prepare_inputs(cfg, params, batch)
    h, _, aux = forward_hidden(
        cfg, params, h, positions=positions, enc_out=enc_out,
        prefix_len=prefix_len, causal=True)
    h = final_norm(cfg, params, h)
    if cfg.logits_scaling != 1.0:
        h = h / jnp.asarray(cfg.logits_scaling, h.dtype)
    if prefix_len > 0:
        h = h[:, prefix_len:]  # loss only over text tokens

    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
    mask = batch.get("loss_mask", jnp.ones((b, s_tok), jnp.float32))
    mask = mask.at[:, -1].set(0.0)

    loss = layers.chunked_cross_entropy(
        h, _unembed(cfg, params), targets, mask,
        chunk=cfg.loss_chunk, final_softcap=cfg.final_softcap)
    total = loss + cfg.router_aux_coef * aux
    return total, {"ce": loss, "aux": aux}


def prefill(
    cfg: ModelConfig, params: Params, batch: dict[str, jax.Array], *, max_seq: int
) -> tuple[jax.Array, Params]:
    """Process the prompt, fill caches, return last-position logits."""
    tokens = batch["tokens"]
    b = tokens.shape[0]
    h, enc_out, positions, prefix_len = _prepare_inputs(cfg, params, batch)
    caches = init_cache(cfg, b, max_seq, enc_seq=enc_out.shape[1] if enc_out is not None else None)
    h, caches, _ = forward_hidden(
        cfg, params, h, positions=positions, caches=caches,
        enc_out=enc_out, prefix_len=prefix_len, causal=True)
    return head_logits(cfg, params, h[:, -1:]), caches


def _add_decode_positions(
    cfg: ModelConfig, h: jax.Array, positions: jax.Array
) -> jax.Array:
    """Absolute sinusoidal embeddings at traced decode positions.

    Prefill adds them in ``_prepare_inputs``; decode must add the same rows
    at each slot's live position or sinusoidal models (whisper) decode with
    no position signal at all.  No-op for rope/NoPE configs.
    """
    if not cfg.sinusoidal_pos:
        return h
    return h + layers.sinusoidal_positions_at(
        positions, cfg.d_model, cfg.compute_dtype)


def decode_step(
    cfg: ModelConfig, params: Params, tokens: jax.Array, caches: Params,
    cur_len: jax.Array,
) -> tuple[jax.Array, Params]:
    """One decode step: tokens (B,1) at absolute position cur_len.

    ``cur_len`` is a scalar (whole batch at one position) or a (B,) vector
    (continuous batching: per-slot positions; rope, cache writes and the
    attention mask are then applied per row).
    """
    h = meshutil.shard_batch(_embed_tokens(cfg, params, tokens))
    positions = cur_len[None] if jnp.ndim(cur_len) == 0 else cur_len[:, None]
    h = _add_decode_positions(cfg, h, positions)
    h, caches, _ = forward_hidden(
        cfg, params, h, positions=positions, caches=caches, cur_len=cur_len)
    return head_logits(cfg, params, h), caches


def decode_step_paged(
    cfg: ModelConfig, params: Params, tokens: jax.Array, caches: Params,
    page_table: jax.Array, cur_len: jax.Array, *, paged_kernel: bool = False,
    count_pairs: bool = False,
) -> tuple[jax.Array, Params]:
    """One decode step against the paged KV pool (see ``init_paged_cache``).

    ``page_table`` (B, n_pages) int32 maps slot b's logical page j to a
    physical pool block; ``cur_len`` must be a (B,) vector (each slot sits at
    its own position).  Rows beyond each slot's ``cur_len`` are masked exactly
    as in :func:`decode_step`, so greedy outputs are token-identical to the
    contiguous path.  ``count_pairs`` adds a third output: the (B,) count
    of each row's routed expert choices that the layers hold (see
    :func:`forward_hidden`).
    """
    assert jnp.ndim(cur_len) == 1, "paged decode needs per-slot positions"
    h = meshutil.shard_batch(_embed_tokens(cfg, params, tokens))
    h = _add_decode_positions(cfg, h, cur_len[:, None])
    h, caches, _, *pairs = forward_hidden(
        cfg, params, h, positions=cur_len[:, None], caches=caches,
        cur_len=cur_len, page_table=page_table, paged_kernel=paged_kernel,
        count_pairs=count_pairs)
    logits = head_logits(cfg, params, h)
    if count_pairs:
        return logits, caches, pairs[0][:, 0]
    return logits, caches


def _multi_unit_check(cfg: ModelConfig, caches: Params | None = None) -> None:
    if any(spec.mixer == "mamba" for spec in cfg.layer_unit):
        raise NotImplementedError(
            "multi-token decode rolls rejected KV writes back by masking; "
            "mamba/hybrid archs advance irreversible per-slot SSM state")
    if caches is None:
        return
    for i, spec in enumerate(cfg.layer_unit):
        c = caches["blocks"].get(f"layer{i}", {})
        window = cfg.spec_window(spec)
        if window > 0 and "k" in c and c["k"].shape[2] == window:
            raise NotImplementedError(
                "multi-token decode needs full-length caches (init_cache "
                "ring=False): scattering a draft block into a ring buffer "
                "overwrites committed keys before acceptance is known — "
                "a rejected draft could never be rolled back")


def decode_step_multi(
    cfg: ModelConfig, params: Params, tokens: jax.Array, caches: Params,
    cur_len: jax.Array,
) -> tuple[jax.Array, Params]:
    """Multi-token decode step: tokens (B, T) at absolute positions
    ``cur_len + [0, T)`` — the speculative verify step's target pass.

    Row b's token t is scored *and written* at position ``cur_len[b] + t``
    with a causal mask inside the block (query t sees keys at positions
    ``<= cur_len[b] + t``), so one jitted call scores a pending token plus
    T-1 draft tokens per slot.  Positions past ``max_seq`` (a padding tail
    beyond the slot's live draft length) are dropped, and rows past a
    slot's accepted prefix are invisible to later steps (masked by
    ``cur_len``) until real decode overwrites them — rejection needs no
    cache mutation on this path.  Returns logits for all T positions
    (B, T, V) and the updated caches.
    """
    assert jnp.ndim(cur_len) == 1, "multi-token decode needs per-slot positions"
    _multi_unit_check(cfg, caches)
    t = tokens.shape[1]
    h = meshutil.shard_batch(_embed_tokens(cfg, params, tokens))
    positions = cur_len[:, None] + jnp.arange(t)[None, :]
    h = _add_decode_positions(cfg, h, positions)
    h, caches, _ = forward_hidden(
        cfg, params, h, positions=positions, caches=caches, cur_len=cur_len)
    return head_logits(cfg, params, h), caches


def decode_step_multi_paged(
    cfg: ModelConfig, params: Params, tokens: jax.Array, caches: Params,
    page_table: jax.Array, cur_len: jax.Array, *, paged_kernel: bool = False,
) -> tuple[jax.Array, Params]:
    """Paged twin of :func:`decode_step_multi`: K/V of the T positions land
    in each slot's pages through the table; positions past a slot's mapped
    pages (padding beyond its live draft length) go to the trash block, so
    a draft block can never corrupt another slot's — or a shared — page."""
    assert jnp.ndim(cur_len) == 1, "paged decode needs per-slot positions"
    _multi_unit_check(cfg)
    t = tokens.shape[1]
    h = meshutil.shard_batch(_embed_tokens(cfg, params, tokens))
    positions = cur_len[:, None] + jnp.arange(t)[None, :]
    h = _add_decode_positions(cfg, h, positions)
    h, caches, _ = forward_hidden(
        cfg, params, h, positions=positions, caches=caches, cur_len=cur_len,
        page_table=page_table, paged_kernel=paged_kernel)
    return head_logits(cfg, params, h), caches


def sample_tokens(
    logits: jax.Array,  # (B, V) f32
    *,
    temperature: float = 0.0,
    key: jax.Array | None = None,
) -> jax.Array:
    """On-device sampling of the next token per row -> (B,) int32.

    Greedy when ``temperature == 0``.  For temperature sampling ``key`` is
    either one PRNG key shared by the batch (one categorical draw over the
    batch, matching ``ServingEngine.generate``) or a (B, 2) batch of per-row
    keys (continuous batching: each slot draws from its own key stream).
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if key is None:
        raise ValueError("temperature sampling needs a PRNG key")
    scaled = logits / temperature
    if key.ndim > 1:  # per-row keys
        return jax.vmap(jax.random.categorical)(key, scaled).astype(jnp.int32)
    return jax.random.categorical(key, scaled).astype(jnp.int32)


def decode_and_sample(
    cfg: ModelConfig, params: Params, tokens: jax.Array, caches: Params,
    cur_len: jax.Array, *, temperature: float = 0.0,
    key: jax.Array | None = None,
) -> tuple[jax.Array, Params]:
    """Decode step with sampling fused into the jitted graph: returns the
    sampled (B,) int32 tokens and the caches — the caller transfers one int32
    per slot per tick instead of a (B, V) logits round-trip."""
    logits, caches = decode_step(cfg, params, tokens, caches, cur_len)
    return sample_tokens(
        logits[:, -1], temperature=temperature, key=key), caches


def decode_and_sample_paged(
    cfg: ModelConfig, params: Params, tokens: jax.Array, caches: Params,
    page_table: jax.Array, cur_len: jax.Array, *, temperature: float = 0.0,
    key: jax.Array | None = None, paged_kernel: bool = False,
    count_pairs: bool = False,
) -> tuple[jax.Array, Params]:
    """Paged twin of :func:`decode_and_sample` (``count_pairs``: see
    :func:`decode_step_paged`)."""
    logits, caches, *pairs = decode_step_paged(
        cfg, params, tokens, caches, page_table, cur_len,
        paged_kernel=paged_kernel, count_pairs=count_pairs)
    toks = sample_tokens(logits[:, -1], temperature=temperature, key=key)
    return (toks, caches, *pairs)
