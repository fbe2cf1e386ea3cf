"""Pure-jnp oracles for every kernel (the allclose ground truth)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def matmul_ref(x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.dot(x, y, preferred_element_type=jnp.float32).astype(
        jnp.result_type(x.dtype, y.dtype))


def attention_ref(
    q: jax.Array,  # (BH, Sq, hd)
    k: jax.Array,  # (BH, Sk, hd)
    v: jax.Array,  # (BH, Sk, hd)
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    scale: float,
) -> jax.Array:
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    sq, sk = q.shape[1], k.shape[1]
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    ok = jnp.ones((sq, sk), bool)
    if causal:
        ok = qpos >= kpos
    if window > 0:
        ok = ok & (qpos - kpos < window)
    s = jnp.where(ok[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _layer_of(layer, *pools):
    """``pools`` as they are, or with ``layer`` each stack's that layer."""
    return pools if layer is None else tuple(p[layer] for p in pools)


def paged_attention_ref(
    q: jax.Array,  # (B, H, hd) single-token queries (H = Hkv * G)
    k_pool: jax.Array,  # (num_blocks, block_size, Hkv, hd)
    v_pool: jax.Array,  # (num_blocks, block_size, Hkv, hd)
    page_table: jax.Array,  # (B, n_pages) int32
    cur_len: jax.Array,  # (B,) int32
    *,
    window: int = 0,
    softcap: float = 0.0,
    scale: float,
    layer: jax.Array | int | None = None,
) -> jax.Array:
    """Pure-jnp oracle for the paged decode-attention kernel: gather each
    row's pages into a contiguous logical view, then masked attention with
    the per-row ``cur_len`` visibility cut. With ``layer`` the pools are
    stacked ``(layers, num_blocks, ...)`` and that layer's are read."""
    k_pool, v_pool = _layer_of(layer, k_pool, v_pool)
    b, h, hd = q.shape
    nb, bs, hkv, _ = k_pool.shape
    g = h // hkv
    n_pages = page_table.shape[1]
    s_log = n_pages * bs
    k = k_pool[page_table].reshape(b, s_log, hkv, hd)
    v = v_pool[page_table].reshape(b, s_log, hkv, hd)
    kf = jnp.broadcast_to(
        k[:, :, :, None], (b, s_log, hkv, g, hd)).reshape(b, s_log, h, hd)
    vf = jnp.broadcast_to(
        v[:, :, :, None], (b, s_log, hkv, g, hd)).reshape(b, s_log, h, hd)

    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                   kf.astype(jnp.float32)) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    pos = jnp.arange(s_log)[None, :]  # (1, S)
    cl = cur_len.astype(jnp.int32)[:, None]  # (B, 1)
    ok = pos <= cl
    if window > 0:
        ok = ok & (cl - pos < window)
    s = jnp.where(ok[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", p, vf.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_attention_multi_ref(
    q: jax.Array,  # (B, T, H, hd): T-token draft block per slot
    k_pool: jax.Array,  # (num_blocks, block_size, Hkv, hd)
    v_pool: jax.Array,  # (num_blocks, block_size, Hkv, hd)
    page_table: jax.Array,  # (B, n_pages) int32
    cur_len: jax.Array,  # (B,) int32: absolute position of token 0 per slot
    *,
    window: int = 0,
    softcap: float = 0.0,
    scale: float,
    layer: jax.Array | int | None = None,
) -> jax.Array:
    """Oracle for the q_len>1 paged decode kernel: gather each row's pages,
    then attention with the per-query causal cut — query t at absolute
    position ``cur_len + t`` sees keys at positions ``<= cur_len + t``.
    ``layer``: as in :func:`paged_attention_ref`."""
    k_pool, v_pool = _layer_of(layer, k_pool, v_pool)
    b, t, h, hd = q.shape
    nb, bs, hkv, _ = k_pool.shape
    g = h // hkv
    n_pages = page_table.shape[1]
    s_log = n_pages * bs
    k = k_pool[page_table].reshape(b, s_log, hkv, hd)
    v = v_pool[page_table].reshape(b, s_log, hkv, hd)
    kf = jnp.broadcast_to(
        k[:, :, :, None], (b, s_log, hkv, g, hd)).reshape(b, s_log, h, hd)
    vf = jnp.broadcast_to(
        v[:, :, :, None], (b, s_log, hkv, g, hd)).reshape(b, s_log, h, hd)

    s = jnp.einsum("bthd,bkhd->bhtk", q.astype(jnp.float32),
                   kf.astype(jnp.float32)) * scale
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    pos = jnp.arange(s_log)[None, None, :]  # (1, 1, S)
    qpos = (cur_len.astype(jnp.int32)[:, None, None]
            + jnp.arange(t)[None, :, None])  # (B, T, 1)
    ok = pos <= qpos
    if window > 0:
        ok = ok & (qpos - pos < window)
    s = jnp.where(ok[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhtk,bkhd->bthd", p, vf.astype(jnp.float32))
    return out.astype(q.dtype)


def _dequant_pool(pool: jax.Array, scale: jax.Array) -> jax.Array:
    """(num_blocks, bs, hkv, hd) codes x (num_blocks, hkv) scales -> f32."""
    return pool.astype(jnp.float32) * scale[:, None, :, None]


def paged_attention_quant_ref(
    q, k_pool, v_pool, k_scale, v_scale, page_table, cur_len, *,
    window: int = 0, softcap: float = 0.0, scale: float,
    layer: jax.Array | int | None = None,
) -> jax.Array:
    """Oracle for the fused-dequant paged kernel: dequantize the whole pool
    up front (exactly codes * scale, the value the kernel reconstructs
    per block), then the existing paged oracle."""
    k_pool, v_pool, k_scale, v_scale = _layer_of(
        layer, k_pool, v_pool, k_scale, v_scale)
    return paged_attention_ref(
        q, _dequant_pool(k_pool, k_scale), _dequant_pool(v_pool, v_scale),
        page_table, cur_len, window=window, softcap=softcap, scale=scale)


def paged_attention_multi_quant_ref(
    q, k_pool, v_pool, k_scale, v_scale, page_table, cur_len, *,
    window: int = 0, softcap: float = 0.0, scale: float,
    layer: jax.Array | int | None = None,
) -> jax.Array:
    """q_len>1 twin of :func:`paged_attention_quant_ref`."""
    k_pool, v_pool, k_scale, v_scale = _layer_of(
        layer, k_pool, v_pool, k_scale, v_scale)
    return paged_attention_multi_ref(
        q, _dequant_pool(k_pool, k_scale), _dequant_pool(v_pool, v_scale),
        page_table, cur_len, window=window, softcap=softcap, scale=scale)


def fwt_ref(x: jax.Array) -> jax.Array:
    """Unnormalized Walsh-Hadamard transform over the last axis."""
    n = x.shape[-1]
    assert n & (n - 1) == 0
    out = x.astype(jnp.float32)
    h = 1
    while h < n:
        out = out.reshape(*x.shape[:-1], n // (2 * h), 2, h)
        a, b = out[..., 0, :], out[..., 1, :]
        out = jnp.stack([a + b, a - b], axis=-2).reshape(*x.shape[:-1], n)
        h *= 2
    return out.astype(x.dtype)


def nw_ref(
    north: np.ndarray,  # (B,)
    west: np.ndarray,  # (B,)
    corner: float,
    sub: np.ndarray,  # (B, B)
    *,
    gap: float = 1.0,
) -> np.ndarray:
    """Sequential double-loop NW tile (numpy oracle)."""
    b = sub.shape[0]
    h = np.zeros((b + 1, b + 1), np.float32)
    h[0, 0] = corner
    h[0, 1:] = np.asarray(north, np.float32)
    h[1:, 0] = np.asarray(west, np.float32)
    for i in range(1, b + 1):
        for j in range(1, b + 1):
            h[i, j] = max(
                h[i - 1, j - 1] + sub[i - 1, j - 1],
                h[i - 1, j] - gap,
                h[i, j - 1] - gap,
            )
    return h[1:, 1:]


def nw_full_ref(seq_scores: np.ndarray, *, gap: float = 1.0) -> np.ndarray:
    """Full NW matrix for an (n, m) substitution score matrix with zero
    boundary initialized to -i*gap / -j*gap (standard global alignment)."""
    n, m = seq_scores.shape
    h = np.zeros((n + 1, m + 1), np.float32)
    h[0, :] = -gap * np.arange(m + 1)
    h[:, 0] = -gap * np.arange(n + 1)
    for i in range(1, n + 1):
        for j in range(1, m + 1):
            h[i, j] = max(
                h[i - 1, j - 1] + seq_scores[i - 1, j - 1],
                h[i - 1, j] - gap,
                h[i, j - 1] - gap,
            )
    return h[1:, 1:]
