"""Attention: streamed (flash-style) reference implementation + decode path.

``flash_attention_ref`` is the pure-JAX oracle/production-CPU twin of the
Pallas kernel in ``repro.kernels.flash_attention``.  It streams over
(q-block, kv-block) *task pairs* with an online softmax -- the paper's
Independent/False-dependent streaming applied to attention:

  * the KV blocks are read-only data shared by all q-block tasks (RAR --
    false dependence, handled by replaying KV blocks per q block);
  * only block pairs that can contain unmasked entries are enumerated
    (causal lower triangle / sliding-window band), so HLO FLOPs match the
    real work -- no S^2 waste on masked blocks.  This matters for the
    roofline: masked-out compute would otherwise inflate the compute term.

Supports GQA (grouped KV heads), RoPE (applied by the caller), logit
softcap (gemma2), sliding windows (gemma2 local layers, mixtral), prefix-LM
bidirectional masking (paligemma) and bidirectional encoders (whisper).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import quant
from repro.models import layers

Params = dict[str, Any]

NEG_INF = -1e30


def _pick_chunk(s: int, chunk: int, *, at_least: int = 0) -> int:
    """Largest block size <= chunk dividing s (and >= the prefix if any)."""
    for c in range(min(chunk, s), 0, -1):
        if s % c == 0 and (at_least == 0 or c >= at_least):
            return c
    for c in range(max(1, at_least), s + 1):
        if s % c == 0:
            return c
    return s


def _block_pairs(
    n_q: int, n_k: int, *, causal: bool, window: int, chunk_q: int,
    chunk_k: int, q_offset: int = 0, prefix_len: int = 0,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Static (qi, kj) block-pair lists + deduplicated block masks.

    causal: keep pairs whose youngest q >= oldest k.  window > 0: keep block
    pairs overlapping the band q - k < window.  Bidirectional: all pairs.

    Masks are computed at trace time in numpy and deduplicated: almost all
    pairs share one of a handful of patterns (all-valid interior blocks, the
    triangular diagonal block, band-edge blocks), so the kernel gathers a
    (U, cq, ck) constant instead of recomputing masks per step -- which XLA
    would otherwise hoist out of the loop as a giant (n_pairs, B, H, cq, ck)
    buffer.

    Returns (qi, kj, mask_id, masks) device arrays.
    """
    pairs: list[tuple[int, int]] = []
    mask_ids: list[int] = []
    unique: dict[bytes, int] = {}
    masks: list[np.ndarray] = []
    oq = np.arange(chunk_q)
    ok_ = np.arange(chunk_k)
    for qi in range(n_q):
        for kj in range(n_k):
            q_lo = qi * chunk_q + q_offset
            q_hi = q_lo + chunk_q - 1
            k_lo = kj * chunk_k
            k_hi = k_lo + chunk_k - 1
            if causal and k_lo > q_hi and not (prefix_len > 0 and k_lo < prefix_len):
                continue
            if window > 0 and q_lo - k_hi >= window:
                continue
            qpos = q_lo + oq
            kpos = k_lo + ok_
            m = np.ones((chunk_q, chunk_k), bool)
            if causal:
                m = qpos[:, None] >= kpos[None, :]
                if prefix_len > 0:
                    m = m | (kpos[None, :] < prefix_len)
            if window > 0:
                m = m & (qpos[:, None] - kpos[None, :] < window)
            if not m.any():
                continue
            key = m.tobytes()
            if key not in unique:
                unique[key] = len(masks)
                masks.append(m)
            pairs.append((qi, kj))
            mask_ids.append(unique[key])
    qs = jnp.asarray([p[0] for p in pairs], jnp.int32)
    ks = jnp.asarray([p[1] for p in pairs], jnp.int32)
    ids = jnp.asarray(mask_ids, jnp.int32)
    # Additive f32 masks (0 / NEG_INF): an additive mask stays fused into the
    # score computation, whereas a boolean select's broadcast gets hoisted by
    # XLA into a (n_pairs, B, H, cq, ck) loop-invariant buffer.
    addm = np.where(np.stack(masks), 0.0, NEG_INF).astype(np.float32)
    return qs, ks, ids, jnp.asarray(addm)


def _broadcast_kv(k: jax.Array, v: jax.Array, g: int) -> tuple[jax.Array, jax.Array]:
    """(B,S,Hkv,hd) -> (B,S,Hkv*g,hd): replicate KV across each GQA group."""
    b, s, hkv, hd = k.shape
    k = jnp.broadcast_to(k[:, :, :, None], (b, s, hkv, g, hd)).reshape(b, s, hkv * g, hd)
    v = jnp.broadcast_to(v[:, :, :, None], (b, s, hkv, g, hd)).reshape(b, s, hkv * g, hd)
    return k, v


def flash_attention_ref(
    q: jax.Array,  # (B, Sq, H, hd) flat query heads (H = Hkv * G)
    k: jax.Array,  # (B, Sk, Hkv, hd)
    v: jax.Array,  # (B, Sk, Hkv, hd)
    *,
    chunk: int = 512,
    causal: bool = True,
    window: int = 0,
    prefix_len: int = 0,
    softcap_val: float = 0.0,
    scale: float | None = None,
    q_offset: int = 0,
) -> jax.Array:
    """Streamed online-softmax attention over block pairs, with a flash-style
    custom VJP: the backward pass *recomputes* P per block pair instead of
    saving an (n_pairs, B, H, cq, ck) stack -- the streaming trade (recompute
    over store) that keeps the memory roofline term at O(S) per layer.
    """
    hd = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    cfg = (int(chunk), bool(causal), int(window), int(prefix_len),
           float(softcap_val), float(scale), int(q_offset))
    return _flash(cfg, q, k, v)


def _flash_setup(cfg, q_shape, k_shape):
    chunk, causal, window, prefix_len, softcap_val, scale, q_offset = cfg
    b, sq, h, hd = q_shape
    sk = k_shape[1]
    chunk_q = _pick_chunk(sq, chunk, at_least=prefix_len)
    chunk_k = _pick_chunk(sk, chunk)
    n_q, n_k = sq // chunk_q, sk // chunk_k
    if prefix_len > 0:
        assert chunk_q >= prefix_len, "attn chunk must cover the bidirectional prefix"
    pairs = _block_pairs(
        n_q, n_k, causal=causal, window=window, chunk_q=chunk_q,
        chunk_k=chunk_k, q_offset=q_offset, prefix_len=prefix_len)
    return chunk_q, chunk_k, n_q, n_k, pairs


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg, q, k, v):
    out, _ = _flash_fwd_impl(cfg, q, k, v)
    return out


def _flash_fwd_impl(cfg, q, k, v):
    chunk, causal, window, prefix_len, softcap_val, scale, q_offset = cfg
    b, sq, h, hd = q.shape
    g = h // k.shape[2]
    chunk_q, chunk_k, n_q, n_k, (qi_arr, kj_arr, mask_ids, masks) = _flash_setup(
        cfg, q.shape, k.shape)

    # Flatten GQA groups to full heads and broadcast K/V across each group:
    # with h = n_heads the attention einsums shard over the TP axis even when
    # hkv doesn't divide it (the broadcast of replicated KV is free; the
    # compute then partitions by query head).
    kf, vf = _broadcast_kv(k, v, g)

    # Q/K/V stay in storage dtype (bf16 on TPU): the MXU consumes bf16 with
    # f32 accumulation; the online-softmax state (m, l, acc) stays f32.
    qb = q.reshape(b, n_q, chunk_q, h, hd)
    kb = kf.reshape(b, n_k, chunk_k, h, hd)
    vb = vf.reshape(b, n_k, chunk_k, h, hd)

    m0 = jnp.full((n_q, b, chunk_q, h), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n_q, b, chunk_q, h), jnp.float32)
    acc0 = jnp.zeros((n_q, b, chunk_q, h, hd), jnp.float32)

    def step(carry, pair):
        m, l, acc = carry
        qi, kj, mid = pair
        qc = jax.lax.dynamic_index_in_dim(qb, qi, axis=1, keepdims=False)
        kc = jax.lax.dynamic_index_in_dim(kb, kj, axis=1, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(vb, kj, axis=1, keepdims=False)

        s = jnp.einsum("bqhd,bkhd->bhqk", qc, kc,
                       preferred_element_type=jnp.float32) * scale
        s = layers.softcap(s, softcap_val)

        ok = jax.lax.dynamic_index_in_dim(masks, mid, axis=0, keepdims=False)
        s = s + ok[None, None]

        m_old = jax.lax.dynamic_index_in_dim(m, qi, axis=0, keepdims=False)
        l_old = jax.lax.dynamic_index_in_dim(l, qi, axis=0, keepdims=False)
        acc_old = jax.lax.dynamic_index_in_dim(acc, qi, axis=0, keepdims=False)

        s_max = jnp.moveaxis(s.max(axis=-1), 1, -1)  # (b, q, h)
        m_new = jnp.maximum(m_old, s_max)
        # p: (b, h, q, k); alpha rescales the old accumulator.
        p = jnp.exp(s - jnp.moveaxis(m_new, -1, 1)[..., None])
        alpha = jnp.exp(m_old - m_new)
        l_new = alpha * l_old + jnp.moveaxis(p.sum(-1), 1, -1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vc.dtype), vc,
                        preferred_element_type=jnp.float32)
        acc_new = alpha[..., None] * acc_old + pv

        m = jax.lax.dynamic_update_index_in_dim(m, m_new, qi, axis=0)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, qi, axis=0)
        acc = jax.lax.dynamic_update_index_in_dim(acc, acc_new, qi, axis=0)
        return (m, l, acc), None

    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), (qi_arr, kj_arr, mask_ids))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe[..., None]  # (n_q, b, chunk_q, h, hd)
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq, h, hd).astype(q.dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))  # (n_q, b, chunk_q, h) f32
    return out, lse


def _flash_fwd(cfg, q, k, v):
    out, lse = _flash_fwd_impl(cfg, q, k, v)
    return out, (q, k, v, out, lse)


def _flash_bwd(cfg, res, dout):
    chunk, causal, window, prefix_len, softcap_val, scale, q_offset = cfg
    q, k, v, out, lse = res
    b, sq, h, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    chunk_q, chunk_k, n_q, n_k, (qi_arr, kj_arr, mask_ids, masks) = _flash_setup(
        cfg, q.shape, k.shape)

    kf, vf = _broadcast_kv(k, v, g)
    qb = q.reshape(b, n_q, chunk_q, h, hd)
    kb = kf.reshape(b, n_k, chunk_k, h, hd)
    vb = vf.reshape(b, n_k, chunk_k, h, hd)
    dob = dout.reshape(b, n_q, chunk_q, h, hd)

    # D_i = rowsum(dout * out), one f32 scalar per q row.
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = delta.reshape(b, n_q, chunk_q, h)

    dq0 = jnp.zeros((b, n_q, chunk_q, h, hd), jnp.float32)
    dk0 = jnp.zeros((b, n_k, chunk_k, h, hd), jnp.float32)
    dv0 = jnp.zeros((b, n_k, chunk_k, h, hd), jnp.float32)

    def step(carry, pair):
        dq, dk, dv = carry
        qi, kj, mid = pair
        qc = jax.lax.dynamic_index_in_dim(qb, qi, axis=1, keepdims=False)
        kc = jax.lax.dynamic_index_in_dim(kb, kj, axis=1, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(vb, kj, axis=1, keepdims=False)
        do = jax.lax.dynamic_index_in_dim(dob, qi, axis=1, keepdims=False)
        lse_i = jax.lax.dynamic_index_in_dim(lse, qi, axis=0, keepdims=False)
        dlt_i = jax.lax.dynamic_index_in_dim(delta, qi, axis=1, keepdims=False)

        s_raw = jnp.einsum("bqhd,bkhd->bhqk", qc, kc,
                           preferred_element_type=jnp.float32) * scale
        s_cap = layers.softcap(s_raw, softcap_val)
        ok = jax.lax.dynamic_index_in_dim(masks, mid, axis=0, keepdims=False)
        s_m = s_cap + ok[None, None]
        # flash backward: P recomputed per block pair, never materialized
        p = jnp.exp(s_m - jnp.moveaxis(lse_i, -1, 1)[..., None])  # (b,h,q,k)

        pb = p.astype(vc.dtype)
        dv_c = jnp.einsum("bhqk,bqhd->bkhd", pb, do,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqhd,bkhd->bhqk", do, vc,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - jnp.moveaxis(dlt_i, -1, 1)[..., None])
        if softcap_val > 0.0:
            ds = ds * (1.0 - jnp.square(s_cap / softcap_val))
        ds = ds * scale
        dsb = ds.astype(qc.dtype)
        dq_c = jnp.einsum("bhqk,bkhd->bqhd", dsb, kc,
                          preferred_element_type=jnp.float32)
        dk_c = jnp.einsum("bhqk,bqhd->bkhd", dsb, qc,
                          preferred_element_type=jnp.float32)

        dq = jax.lax.dynamic_update_index_in_dim(
            dq, jax.lax.dynamic_index_in_dim(dq, qi, 1, keepdims=False) + dq_c,
            qi, axis=1)
        dk = jax.lax.dynamic_update_index_in_dim(
            dk, jax.lax.dynamic_index_in_dim(dk, kj, 1, keepdims=False) + dk_c,
            kj, axis=1)
        dv = jax.lax.dynamic_update_index_in_dim(
            dv, jax.lax.dynamic_index_in_dim(dv, kj, 1, keepdims=False) + dv_c,
            kj, axis=1)
        return (dq, dk, dv), None

    (dq, dk, dv), _ = jax.lax.scan(step, (dq0, dk0, dv0), (qi_arr, kj_arr, mask_ids))

    dq = dq.reshape(b, sq, h, hd).astype(q.dtype)
    # fold the GQA broadcast: sum gradients over each group
    dk = dk.reshape(b, sk, hkv, g, hd).sum(axis=3).astype(k.dtype)
    dv = dv.reshape(b, sk, hkv, g, hd).sum(axis=3).astype(v.dtype)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def decode_attention(
    q: jax.Array,  # (B, T, H, hd): T = 1 (plain decode) or a draft block
    k_cache: jax.Array,  # (B, S, Hkv, hd)
    v_cache: jax.Array,  # (B, S, Hkv, hd)
    *,
    cur_len: jax.Array,  # int32 scalar or (B,): index of the token generated
    window: int = 0,
    softcap_val: float = 0.0,
    scale: float | None = None,
) -> jax.Array:
    """Decode attention against a (possibly ring-buffered) KV cache.

    ``cur_len`` may be per-batch (continuous batching: each slot sits at its
    own position), in which case the visibility mask is computed per row.
    With ``T > 1`` (speculative multi-token decode) query ``t`` sits at
    absolute position ``cur_len + t`` and its mask is causal within the
    block: key ``p`` is visible iff ``p <= cur_len + t`` (and inside the
    window) — the per-slot variable-length query block of the verify step.
    """
    b, s, hkv, hd = k_cache.shape
    t = q.shape[1]
    g = q.shape[2] // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    kf, vf = _broadcast_kv(k_cache, v_cache, g)  # (B,S,H,hd)

    sc = jnp.einsum("bqhd,bkhd->bhqk", q, kf.astype(q.dtype),
                    preferred_element_type=jnp.float32) * scale
    sc = layers.softcap(sc, softcap_val)
    slot = jnp.arange(s)[None, None, :]  # (1, 1, S)
    cl = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (b,))
    qpos = cl[:, None, None] + jnp.arange(t)[None, :, None]  # (B, T, 1)
    if window > 0 and s == window:
        # Ring buffer: slot s holds original position p ≡ s (mod window) with
        # p <= qpos; valid once written.
        ok = (slot <= qpos) | (qpos >= window)
    else:
        ok = slot <= qpos
        if window > 0:
            ok = ok & (qpos - slot < window)
    sc = jnp.where(ok[:, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vf.dtype), vf,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def paged_decode_attention(
    q: jax.Array,  # (B, T, H, hd): T = 1, or a speculative draft block
    k_pool: jax.Array,  # (num_blocks, block_size, Hkv, hd) global page pool
    v_pool: jax.Array,  # (num_blocks, block_size, Hkv, hd)
    page_table: jax.Array,  # (B, n_pages) int32: logical page -> pool block
    *,
    cur_len: jax.Array,  # (B,) int32: index of the token generated per row
    window: int = 0,
    softcap_val: float = 0.0,
    scale: float | None = None,
    k_scale: jax.Array | None = None,  # (num_blocks, Hkv) per-page scales
    v_scale: jax.Array | None = None,
    layer: jax.Array | int | None = None,  # index into stacked pools
) -> jax.Array:
    """Decode attention against the paged KV pool (gather reference).

    Each row's logical sequence is the concatenation of its page-table
    entries (position p lives in page ``p // block_size`` at offset
    ``p % block_size``); after the gather the per-row ``cur_len`` visibility
    mask is applied exactly as in :func:`decode_attention`, so unallocated /
    stale pages (mapped to the trash block) never contribute.  The Pallas
    kernel twin (``repro.kernels.paged_attention``) streams the same pages
    block-wise without materializing the gathered view in HBM.

    With ``k_scale``/``v_scale`` the pools hold quantized codes and the
    gather dequantizes per (page, kv-head) before attending. With ``layer``
    the pools (and scales) are stacked ``(layers, num_blocks, ...)`` and
    the gather reads that layer's pages in place.
    """
    b, n_pages = page_table.shape
    bs, hkv, hd = k_pool.shape[-3:]
    at = (page_table,) if layer is None else (layer, page_table)
    k = k_pool[at]  # (B, n_pages, bs, hkv, hd)
    v = v_pool[at]
    if k_scale is not None:
        k = quant.dequantize(k, k_scale[at])
        v = quant.dequantize(v, v_scale[at])
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    k = k.reshape(b, n_pages * bs, hkv, hd)
    v = v.reshape(b, n_pages * bs, hkv, hd)
    return decode_attention(
        q, k, v, cur_len=cur_len, window=window, softcap_val=softcap_val,
        scale=scale)


def _kv_dtype_of(cache: dict[str, jax.Array]) -> str:
    return "int8" if cache["k"].dtype == jnp.int8 else "fp8"


def _quant_paged_write(
    pool: jax.Array,  # (layers, num_blocks, bs, hkv, hd) quantized codes
    scale_pool: jax.Array,  # (layers, num_blocks, hkv) f32 per-page scales
    layer: jax.Array,  # the layer written
    rows: jax.Array,  # (B, S, hkv, hd) new full-precision rows
    page: jax.Array,  # (B, S) physical block per row-position
    off: jax.Array,  # (B, S) in-page offset per row-position
    kv_dtype: str,
) -> tuple[jax.Array, jax.Array]:
    """Decode-write into quantized pages with rescale-on-grow.

    Each written row may exceed its page's current scale, so the page's
    scale grows to cover it (max of old and the row's absmax/QMAX) and the
    existing codes are requantized at the new scale — an exact identity
    when the scale does not change (ratio 1 round-trips both int8 and
    fp8).  A freshly-faulted page (offset 0) carries a stale scale from
    its previous owner, which must be ignored or resolution collapses.

    Positions are processed sequentially (S is 1 for plain decode, the
    draft length for speculative decode) so two draft rows landing on the
    same page compose.  Duplicate pages across batch rows only occur at
    the trash block 0, where any finite garbage is acceptable.
    """
    b, s = page.shape
    bidx = jnp.arange(b)
    for t in range(s):
        pg = page[:, t]  # (B,)
        ot = off[:, t]  # (B,)
        row = rows[:, t].astype(jnp.float32)  # (B, hkv, hd)
        old_s = scale_pool[layer, pg]  # (B, hkv)
        old_eff = jnp.where(ot[:, None] == 0, 0.0, old_s)
        row_s = jnp.max(jnp.abs(row), axis=-1) / quant.qmax(kv_dtype)
        new_s = jnp.maximum(old_eff, row_s)
        base = quant.dequantize(pool[layer, pg], old_eff)  # (B, bs, hkv, hd)
        merged = base.at[bidx, ot].set(row)
        codes = quant.quantize(merged, new_s, kv_dtype)
        pool = pool.at[layer, pg].set(codes)
        scale_pool = scale_pool.at[layer, pg].set(new_s)
    return pool, scale_pool


# ----------------------------------------------------------------------------
# Full multi-head attention layer (projections + rope + cache handling).
# ----------------------------------------------------------------------------


def attention_init(
    key,
    *,
    d_model: int,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    dtype,
    qk_norm: bool = False,
    cross: bool = False,
) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": layers.dense_init(ks[0], (d_model, n_heads * head_dim), dtype),
        "wk": layers.dense_init(ks[1], (d_model, n_kv_heads * head_dim), dtype),
        "wv": layers.dense_init(ks[2], (d_model, n_kv_heads * head_dim), dtype),
        "wo": layers.dense_init(ks[3], (n_heads * head_dim, d_model), dtype),
    }
    if qk_norm:
        p["q_norm"] = layers.rmsnorm_init(head_dim, dtype)
        p["k_norm"] = layers.rmsnorm_init(head_dim, dtype)
    return p


def attention_apply(
    p: Params,
    x: jax.Array,  # (B, S, D)
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    positions: jax.Array | None = None,  # (S,) absolute positions; None = no rope
    rope_theta: float = 1e4,
    causal: bool = True,
    window: int = 0,
    prefix_len: int = 0,
    softcap_val: float = 0.0,
    scale: float | None = None,
    chunk: int = 512,
    qk_norm: bool = False,
    kv_source: jax.Array | None = None,  # cross-attention keys/values source
    cache: dict[str, jax.Array] | None = None,  # decode: {"k","v"} (B,S,hkv,hd)
    cur_len: jax.Array | None = None,  # decode: scalar current position
    q_offset: int = 0,  # static chunk offset for streamed (chunked) prefill
    page_table: jax.Array | None = None,  # paged decode: (B, n_pages) int32
    paged_kernel: bool = False,  # paged decode via the Pallas pool kernel
    norm_eps: float = 1e-6,  # the q/k RMSNorm's epsilon
    layer: jax.Array | None = None,  # paged: the layer of the stacked pools
) -> tuple[jax.Array, dict[str, jax.Array] | None]:
    """Returns (output (B,S,D), updated cache or None).

    With a ``page_table`` the cache leaves are the model's stacked page
    pools ``(layers, num_blocks, block_size, hkv, hd)`` (and scales
    ``(layers, num_blocks, hkv)``): this layer's rows are written at
    ``[layer, page, offset]`` and read back through the table, so the
    returned pools are the same buffers updated in place."""
    b, s, d = x.shape
    kv_in = x if kv_source is None else kv_source

    # Flat head layout: the model axis shards n_heads * head_dim cleanly.
    q = (x @ p["wq"]).reshape(b, s, n_heads, head_dim)
    if cache is not None and kv_source is not None and "k" in cache and cur_len is None:
        # Cross-attention decode: KV precomputed once at prefill.
        k, v = cache["k"], cache["v"]
    else:
        k = (kv_in @ p["wk"]).reshape(b, kv_in.shape[1], n_kv_heads, head_dim)
        v = (kv_in @ p["wv"]).reshape(b, kv_in.shape[1], n_kv_heads, head_dim)

    if qk_norm:
        q = layers.rmsnorm(p["q_norm"], q, eps=norm_eps)
        k = layers.rmsnorm(p["k_norm"], k, eps=norm_eps)

    if positions is not None and kv_source is None:
        sin, cos = layers.rope_angles(positions, head_dim, rope_theta)
        if positions.ndim == 1:  # shared positions: add the batch axis
            sin, cos = sin[None], cos[None]
        q = layers.apply_rope(q, sin, cos)
        if cur_len is None or k.shape[1] == s:  # fresh K (not from cache)
            k = layers.apply_rope(k, sin, cos)

    new_cache = cache
    if (cur_len is not None and cache is not None and kv_source is None
            and page_table is not None):
        # Paged decode: the cache leaves are the stacked page pools
        # (layers, num_blocks, block_size, hkv, hd).  Row i's s-token block
        # lands at its slot's positions cur_len..cur_len+s-1 of this layer
        # through the page-table indirection; free slots map to the trash
        # block, so their padding writes never touch live pages.  Positions
        # past the table (a draft block's padding tail) are routed to the
        # trash block too — the engine only ensures pages through each
        # slot's live draft length.
        bs_pg = cache["k"].shape[2]
        n_pages = page_table.shape[1]
        pos = cur_len[:, None] + jnp.arange(s)[None, :]  # (B, S)
        idx = pos // bs_pg
        page = jnp.where(
            idx < n_pages,
            jnp.take_along_axis(page_table, jnp.minimum(idx, n_pages - 1),
                                axis=1),
            0)  # (B, S) physical block ids (0 = trash)
        off = pos % bs_pg
        quantized = "k_scale" in cache
        if quantized:
            kv_dtype = _kv_dtype_of(cache)
            k_pool, ks_pool = _quant_paged_write(
                cache["k"], cache["k_scale"], layer, k, page, off, kv_dtype)
            v_pool, vs_pool = _quant_paged_write(
                cache["v"], cache["v_scale"], layer, v, page, off, kv_dtype)
            new_cache = {"k": k_pool, "v": v_pool,
                         "k_scale": ks_pool, "v_scale": vs_pool}
        else:
            k_pool = cache["k"].at[layer, page, off].set(
                k.astype(cache["k"].dtype))
            v_pool = cache["v"].at[layer, page, off].set(
                v.astype(cache["v"].dtype))
            ks_pool = vs_pool = None
            new_cache = {"k": k_pool, "v": v_pool}
        if paged_kernel:
            from repro.kernels import ops as _kops
            if s == 1:
                if quantized:
                    out = _kops.paged_attention_quant(
                        q[:, 0], k_pool, v_pool, ks_pool, vs_pool,
                        page_table, cur_len, window=window,
                        softcap=softcap_val, scale=scale,
                        layer=layer)[:, None]
                else:
                    out = _kops.paged_attention(
                        q[:, 0], k_pool, v_pool, page_table, cur_len,
                        window=window, softcap=softcap_val,
                        scale=scale, layer=layer)[:, None]
            else:
                if quantized:
                    out = _kops.paged_attention_multi_quant(
                        q, k_pool, v_pool, ks_pool, vs_pool, page_table,
                        cur_len, window=window, softcap=softcap_val,
                        scale=scale, layer=layer)
                else:
                    out = _kops.paged_attention_multi(
                        q, k_pool, v_pool, page_table, cur_len,
                        window=window, softcap=softcap_val, scale=scale,
                        layer=layer)
        else:
            out = paged_decode_attention(
                q, k_pool, v_pool, page_table, cur_len=cur_len, window=window,
                softcap_val=softcap_val, scale=scale,
                k_scale=ks_pool, v_scale=vs_pool, layer=layer)
    elif cur_len is not None and cache is not None and kv_source is None:
        # Decode: write this step's K/V into the cache (ring-buffered if SWA).
        s_cache = cache["k"].shape[1]
        ring = window > 0 and s_cache == window
        if jnp.ndim(cur_len) == 0:
            write_at = jnp.mod(cur_len, window) if ring else cur_len
            k_cache = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, write_at, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, write_at, 0, 0))
        else:
            # Per-slot positions (continuous batching): scatter row i's
            # s-token K/V block at its own write offsets.  Positions past
            # max_seq (a draft block's padding tail) are dropped.
            bidx = jnp.arange(b)[:, None]
            wpos = cur_len[:, None] + jnp.arange(s)[None, :]  # (B, S)
            if ring:
                wpos = jnp.mod(wpos, window)
            k_cache = cache["k"].at[bidx, wpos].set(
                k.astype(cache["k"].dtype), mode="drop")
            v_cache = cache["v"].at[bidx, wpos].set(
                v.astype(cache["v"].dtype), mode="drop")
        new_cache = {"k": k_cache, "v": v_cache}
        out = decode_attention(
            q, k_cache, v_cache, cur_len=cur_len, window=window,
            softcap_val=softcap_val, scale=scale,
        )
    elif page_table is not None and cache is not None and kv_source is None:
        # Fused prefill -> page scatter (cur_len is None): write this
        # chunk's K/V projections directly into this layer's pool pages
        # through the page table, then attend with the same streamed flash
        # reference over the pool context gathered through the table — no
        # contiguous cache slab, no second jitted scatter.  The chunk's
        # write offsets are
        # static (q_offset..q_offset+s-1), so the touched logical pages
        # form a static set and the per-page writes unroll page-at-a-time.
        #
        # Bitwise parity with the legacy scatter-after-attention path at
        # fp32: the gathered context is statically sliced to exactly
        # q_offset + s positions so ``flash_attention_ref`` sees identical
        # shapes (hence an identical block decomposition via
        # ``_pick_chunk``) and identical values — same h, same K/V bits.
        bs_pg = cache["k"].shape[2]
        n_pages = page_table.shape[1]
        ctx_len = q_offset + s
        assert n_pages * bs_pg >= ctx_len, "fused prefill needs pages for the full context"
        pos_np = q_offset + np.arange(s)
        idx_np = np.minimum(pos_np // bs_pg, n_pages - 1)
        quantized = "k_scale" in cache
        if quantized:
            kv_dtype = _kv_dtype_of(cache)
            k_pool, v_pool = cache["k"], cache["v"]
            ks_pool, vs_pool = cache["k_scale"], cache["v_scale"]
            for li in range(int(idx_np[0]), int(idx_np[-1]) + 1):
                lo_t = max(0, li * bs_pg - q_offset)
                hi_t = min(s, (li + 1) * bs_pg - q_offset)
                off_lo = (q_offset + lo_t) % bs_pg
                pg = page_table[:, li]  # (B,)
                updates = []
                for pool, scale_pool, rows in (
                        (k_pool, ks_pool, k), (v_pool, vs_pool, v)):
                    rows = rows[:, lo_t:hi_t].astype(jnp.float32)
                    old_s = scale_pool[layer, pg]  # (B, hkv)
                    # A page starting at offset 0 is fresh: prefill is
                    # append-only from a page-aligned pos0, so a stale
                    # scale from the page's previous owner is ignored.
                    # off_lo > 0 only happens for the chunk's first page,
                    # partially filled by the previous chunk: merge via
                    # rescale-on-grow exactly as the decode write does.
                    old_eff = (jnp.zeros_like(old_s) if off_lo == 0
                               else old_s)
                    new_s = jnp.maximum(old_eff, quant.scales_of(
                        rows, kv_dtype))
                    base = quant.dequantize(pool[layer, pg], old_eff)
                    merged = base.at[:, off_lo:off_lo + rows.shape[1]].set(
                        rows)
                    codes = quant.quantize(merged, new_s, kv_dtype)
                    updates.append((pool.at[layer, pg].set(codes),
                                    scale_pool.at[layer, pg].set(new_s)))
                (k_pool, ks_pool), (v_pool, vs_pool) = updates
            new_cache = {"k": k_pool, "v": v_pool,
                         "k_scale": ks_pool, "v_scale": vs_pool}
            k_ctx = quant.dequantize(
                k_pool[layer, page_table],
                ks_pool[layer, page_table]).astype(q.dtype)
            v_ctx = quant.dequantize(
                v_pool[layer, page_table],
                vs_pool[layer, page_table]).astype(q.dtype)
        else:
            page = page_table[:, idx_np]  # (B, S) physical blocks
            off = pos_np % bs_pg  # (S,) broadcasts against page
            k_pool = cache["k"].at[layer, page, off].set(
                k.astype(cache["k"].dtype))
            v_pool = cache["v"].at[layer, page, off].set(
                v.astype(cache["v"].dtype))
            new_cache = {"k": k_pool, "v": v_pool}
            k_ctx = k_pool[layer, page_table]
            v_ctx = v_pool[layer, page_table]
        hkv = k.shape[2]
        k_ctx = k_ctx.reshape(b, n_pages * bs_pg, hkv, head_dim)[:, :ctx_len]
        v_ctx = v_ctx.reshape(b, n_pages * bs_pg, hkv, head_dim)[:, :ctx_len]
        out = flash_attention_ref(
            q, k_ctx, v_ctx, chunk=chunk, causal=causal, window=window,
            prefix_len=prefix_len, softcap_val=softcap_val, scale=scale,
            q_offset=q_offset,
        )
    elif q_offset > 0 and cache is not None and kv_source is None:
        # Streamed (chunked) prefill continuation: write this chunk's K/V at
        # the static offset, then attend against the whole context so far --
        # the True-dependent KV handoff between prefill tasks (paper S4.2).
        s_cache = cache["k"].shape[1]
        assert s_cache >= q_offset + s, "streamed prefill needs a full cache"
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, q_offset, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, q_offset, 0, 0))
        new_cache = {"k": k_cache, "v": v_cache}
        k_ctx = k_cache[:, : q_offset + s]
        v_ctx = v_cache[:, : q_offset + s]
        out = flash_attention_ref(
            q, k_ctx, v_ctx, chunk=chunk, causal=causal, window=window,
            prefix_len=prefix_len, softcap_val=softcap_val, scale=scale,
            q_offset=q_offset,
        )
    else:
        out = flash_attention_ref(
            q, k, v, chunk=chunk, causal=causal and kv_source is None,
            window=window, prefix_len=prefix_len, softcap_val=softcap_val,
            scale=scale,
        )
        if cache is not None:
            # Prefill: store the rope'd K and V.  If the cache is a ring
            # buffer (SWA window < prompt), keep only the last `window`
            # positions, rotated so position p lands in slot p % window.
            s_cache = cache["k"].shape[1]
            k_w, v_w = k, v
            if s_cache < k.shape[1]:
                k_w = jnp.roll(k[:, -s_cache:], s % s_cache, axis=1)
                v_w = jnp.roll(v[:, -s_cache:], s % s_cache, axis=1)
            k_cache = jax.lax.dynamic_update_slice(
                cache["k"], k_w.astype(cache["k"].dtype), (0, 0, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(
                cache["v"], v_w.astype(cache["v"].dtype), (0, 0, 0, 0))
            new_cache = {"k": k_cache, "v": v_cache}

    out = out.reshape(b, s, n_heads * head_dim)
    return out @ p["wo"], new_cache


def naive_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: int = 0, prefix_len: int = 0,
    softcap_val: float = 0.0, scale: float | None = None, q_offset: int = 0,
) -> jax.Array:
    """O(S^2)-memory oracle for tests (materializes the score matrix)."""
    b, sq, h, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    k, v = _broadcast_kv(k, v, h // hkv)
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    s = layers.softcap(s, softcap_val)
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(sk)
    ok = jnp.ones((sq, sk), bool)
    if causal:
        ok = qpos[:, None] >= kpos[None, :]
        if prefix_len > 0:
            ok = ok | (kpos[None, :] < prefix_len)
    if window > 0:
        ok = ok & (qpos[:, None] - kpos[None, :] < window)
    s = jnp.where(ok[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
