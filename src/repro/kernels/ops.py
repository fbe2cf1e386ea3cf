"""Jitted public wrappers for the Pallas kernels.

``interpret`` defaults to True on CPU (validation mode) and False on TPU
(real Mosaic lowering) — the TARGET is TPU; this container validates the
kernel bodies in interpret mode against the ref.py oracles.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import fwt as _fwt
from repro.kernels import nw_tile as _nw
from repro.kernels import paged_attention as _pa
from repro.kernels import ssd_chunk as _ssd
from repro.kernels import streamed_matmul as _mm


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret"))
def matmul(x, y, *, block_m=256, block_n=256, block_k=512, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _mm.streamed_matmul(
        x, y, block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "block_q",
                     "block_k", "interpret"))
def flash_attention(
    q,  # (B, S, H, hd)
    k,  # (B, S, Hkv, hd)
    v,
    *,
    causal=True,
    window=0,
    softcap=0.0,
    scale=None,
    block_q=512,
    block_k=512,
    interpret=None,
):
    """GQA flash attention: broadcasts KV per group, flattens (B, H)."""
    interpret = _default_interpret() if interpret is None else interpret
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    kb = jnp.broadcast_to(k[:, :, :, None], (b, k.shape[1], hkv, g, hd))
    vb = jnp.broadcast_to(v[:, :, :, None], (b, v.shape[1], hkv, g, hd))
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kf = kb.reshape(b, k.shape[1], h, hd).transpose(0, 2, 1, 3).reshape(b * h, k.shape[1], hd)
    vf = vb.reshape(b, v.shape[1], h, hd).transpose(0, 2, 1, 3).reshape(b * h, v.shape[1], hd)

    out = _fa.flash_attention_kernel(
        qf, kf, vf, causal=causal, window=window, softcap=softcap,
        scale=scale, block_q=block_q, block_k=block_k, interpret=interpret)
    return out.reshape(b, h, s, hd).transpose(0, 2, 1, 3)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "scale", "interpret"))
def paged_attention(
    q,  # (B, H, hd) single-token queries
    k_pool,  # (num_blocks, block_size, Hkv, hd)
    v_pool,
    page_table,  # (B, n_pages) int32
    cur_len,  # (B,) int32
    *,
    window=0,
    softcap=0.0,
    scale=None,
    layer=None,  # int32 layer index: the pools are stacked over layers
    interpret=None,
):
    """Decode attention directly from the paged KV pool: the page table is
    scalar-prefetched and the kernel copies each row's live pages into VMEM
    itself, instead of materializing a contiguous copy in HBM. With
    ``layer`` the pools are the model's ``(layers, num_blocks, ...)``
    stacks and the kernel's copies read that layer's pages in place."""
    interpret = _default_interpret() if interpret is None else interpret
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return _pa.paged_attention_kernel(
        q, k_pool, v_pool, page_table, cur_len, window=window,
        softcap=softcap, scale=scale, interpret=interpret, layer=layer)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "scale", "interpret"))
def paged_attention_multi(
    q,  # (B, T, H, hd): T-token draft block per slot
    k_pool,  # (num_blocks, block_size, Hkv, hd)
    v_pool,
    page_table,  # (B, n_pages) int32
    cur_len,  # (B,) int32: absolute position of token 0 per slot
    *,
    window=0,
    softcap=0.0,
    scale=None,
    layer=None,  # int32 layer index into stacked pools
    interpret=None,
):
    """q_len>1 paged decode (speculative verify): scores a pending token
    plus T-1 draft tokens per slot in one pass, causal within the block —
    query t sees pool positions ``<= cur_len + t``."""
    interpret = _default_interpret() if interpret is None else interpret
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return _pa.paged_attention_multi_kernel(
        q, k_pool, v_pool, page_table, cur_len, window=window,
        softcap=softcap, scale=scale, interpret=interpret, layer=layer)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "scale", "interpret"))
def paged_attention_quant(
    q,  # (B, H, hd) single-token queries
    k_pool,  # (num_blocks, block_size, Hkv, hd) int8 / fp8 codes
    v_pool,
    k_scale,  # (num_blocks, Hkv) f32 per-page, per-kv-head scales
    v_scale,
    page_table,  # (B, n_pages) int32
    cur_len,  # (B,) int32
    *,
    window=0,
    softcap=0.0,
    scale=None,
    layer=None,  # int32 layer index into stacked pools and scales
    interpret=None,
):
    """Quantized-pool decode attention with dequantization fused into the
    block compute: the DMA moves narrow codes, a page's scales are taken by
    the same physical id, and full-precision K/V never exists in
    pool-resident form."""
    interpret = _default_interpret() if interpret is None else interpret
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return _pa.paged_attention_kernel(
        q, k_pool, v_pool, page_table, cur_len, window=window,
        softcap=softcap, scale=scale, interpret=interpret,
        k_scale=k_scale, v_scale=v_scale, layer=layer)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "scale", "interpret"))
def paged_attention_multi_quant(
    q,  # (B, T, H, hd): T-token draft block per slot
    k_pool,  # (num_blocks, block_size, Hkv, hd) int8 / fp8 codes
    v_pool,
    k_scale,  # (num_blocks, Hkv) f32 per-page, per-kv-head scales
    v_scale,
    page_table,  # (B, n_pages) int32
    cur_len,  # (B,) int32: absolute position of token 0 per slot
    *,
    window=0,
    softcap=0.0,
    scale=None,
    layer=None,  # int32 layer index into stacked pools and scales
    interpret=None,
):
    """Quantized q_len>1 paged decode (speculative verify) with fused
    dequantization — the quant twin of ``paged_attention_multi``."""
    interpret = _default_interpret() if interpret is None else interpret
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return _pa.paged_attention_multi_kernel(
        q, k_pool, v_pool, page_table, cur_len, window=window,
        softcap=softcap, scale=scale, interpret=interpret,
        k_scale=k_scale, v_scale=v_scale, layer=layer)


@functools.partial(jax.jit, static_argnames=("block", "row_tile", "interpret"))
def fwt(x, *, block=None, row_tile=256, interpret=None):
    """Walsh-Hadamard transform of a flat (n,) or batched (r, n) input.

    Kronecker-streamed: WHT(N) = (WHT(B1) x I)(I x WHT(B2)) — two kernel
    passes with a transpose between (the paper's blocked FWT, §4.2).
    """
    interpret = _default_interpret() if interpret is None else interpret
    flat = x.ndim == 1
    if flat:
        n = x.shape[0]
        assert n & (n - 1) == 0
        b2 = block or min(n, 1024)
        b1 = n // b2
        if b1 == 1:
            y = _fwt.fwt_block(x[None, :], row_tile=1, interpret=interpret)[0]
            return y
        xb = x.reshape(b1, b2)
        # pass 1: in-block stages (independent tasks, streamed)
        y = _fwt.fwt_block(xb, row_tile=min(row_tile, b1), interpret=interpret)
        # pass 2: cross-block stages on the transposed layout
        y = y.T.reshape(b2, b1)
        y = _fwt.fwt_block(y, row_tile=min(row_tile, b2), interpret=interpret)
        return y.reshape(b2, b1).T.reshape(n)
    # batched rows: independent tasks
    return _fwt.fwt_block(x, row_tile=min(row_tile, x.shape[0]), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("gap", "interpret"))
def nw_tile(north, west, corner, sub, *, gap=1.0, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _nw.nw_tile(north, west, corner, sub, gap=gap, interpret=interpret)


def nw_wavefront(seq_scores, *, block: int, gap: float = 1.0, interpret=None):
    """Full NW DP matrix via the wavefront scheduler + the tile kernel.

    This is the paper's Fig. 8 pipeline: tiles on an anti-diagonal execute
    concurrently (vmap lanes = streams), diagonals execute in order.
    """
    from repro.core import wavefront

    interpret = _default_interpret() if interpret is None else interpret
    n, m = seq_scores.shape
    assert n % block == 0 and m % block == 0
    rows, cols = n // block, m // block

    sub_tiles = seq_scores.reshape(rows, block, cols, block).transpose(0, 2, 1, 3)

    north_init = -gap * (jnp.arange(cols * block, dtype=jnp.float32) + 1)
    north_init = north_init.reshape(cols, block)
    west_init = -gap * (jnp.arange(rows * block, dtype=jnp.float32) + 1)
    west_init = west_init.reshape(rows, block)
    corner_init = jnp.zeros((rows + 1, cols + 1), jnp.float32)
    corner_init = corner_init.at[0, :].set(
        -gap * block * jnp.arange(cols + 1, dtype=jnp.float32))
    corner_init = corner_init.at[:, 0].set(
        -gap * block * jnp.arange(rows + 1, dtype=jnp.float32))

    def tile_fn(north, west, corner, row_in, col_in, i, j):
        sub = sub_tiles[i, j]  # gather this tile's substitution scores
        tile = _nw.nw_tile(north, west, corner, sub, gap=gap, interpret=interpret)
        return tile, tile[-1, :], tile[:, -1], tile[-1, -1]

    res = wavefront.wavefront_scan(
        tile_fn, rows=rows, cols=cols, block=block,
        north_init=north_init, west_init=west_init, corner_init=corner_init,
    )
    return res.tiles.transpose(0, 2, 1, 3).reshape(n, m)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, a, b_, c_, *, chunk=64, interpret=None):
    """Mamba2 SSD scan via the VMEM-state chunk kernel.

    Same contract as ``repro.models.mamba.ssd_chunked`` (zero init state):
    x (B,S,H,P), dt (B,S,H) positive, a (H,) negative, b_/c_ (B,S,N).
    Returns y (B,S,H,P).
    """
    interpret = _default_interpret() if interpret is None else interpret
    bsz, s, h, p = x.shape
    n = b_.shape[-1]
    xdt = (x * dt[..., None]).transpose(0, 2, 1, 3).reshape(bsz * h, s, p)
    adt = (dt * a[None, None, :]).transpose(0, 2, 1).reshape(bsz * h, s)
    bb = jnp.broadcast_to(b_[:, None], (bsz, h, s, n)).reshape(bsz * h, s, n)
    cc = jnp.broadcast_to(c_[:, None], (bsz, h, s, n)).reshape(bsz * h, s, n)
    y = _ssd.ssd_chunk_kernel(
        xdt.astype(jnp.float32), adt.astype(jnp.float32),
        bb.astype(jnp.float32), cc.astype(jnp.float32),
        chunk=chunk, interpret=interpret)
    return y.reshape(bsz, h, s, p).transpose(0, 2, 1, 3).astype(x.dtype)
