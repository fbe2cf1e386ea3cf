"""Paged decode-attention Pallas kernel: block-wise attention from the pool.

The TPU twin of ``repro.models.attention.paged_decode_attention``: instead
of gathering a slot's pages into a contiguous (B, S, Hkv, hd) view in HBM,
the page table is **scalar-prefetched** and each grid step's K/V BlockSpec
indexes the physical pool block directly — the gather happens inside the
block-fetch DMA, which Mosaic pipelines against the previous page's MXU
compute (the paper's stream overlap, with pages as the Independent transfer
tasks).

Grid: (batch, n_pages) — the page stream is the innermost (sequential)
dimension.  Each step fetches one whole physical page with all its KV heads
(block ``(1, block_size, Hkv, hd)``: the last two block dims equal the
pool's, which is what Mosaic's (8, 128) tiling rule accepts), so a page is
read once per (row, page) rather than once per KV head.  A static loop over
the KV heads runs inside the body; each head keeps its own online-softmax
state (m, l, acc) in VMEM scratch across the page stream, exactly like
``flash_attention``'s KV stream.  Pages fully beyond a row's ``cur_len`` (or
outside its sliding window) skip compute via ``pl.when``; in-page masking is
positional (iota vs ``cur_len``), so trash-page garbage never contributes.

``q_len > 1`` (speculative multi-token decode) folds the query block into
the row dimension: the kernel scores ``q_len * g`` query rows per KV head,
with row ``r``'s query sitting at absolute position ``cur_len + r // g`` —
the causal-within-the-block mask of the verify step.  A page is skipped
only when *every* query in the block masks it (the youngest query bounds
the causal cut, the oldest bounds the window cut).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(
    pt_ref,  # SMEM (B, n_pages) int32: scalar-prefetched page table
    cl_ref,  # SMEM (B,) int32: per-row current position
    q_ref,  # (1, Hkv, q_len * g, hd)
    k_ref,  # (1, bs, Hkv, hd): one physical page, all kv heads
    v_ref,  # (1, bs, Hkv, hd)
    *rest,  # quantized: (ks_ref, vs_ref, o_ref, m, l, acc) — the per-page
    # per-head f32 scales, (1, 1, Hkv), ride the same scalar-prefetched
    # indexing as the page itself, so dequantization is fused into the
    # block compute (the pool's narrow codes are what the DMA moves); else
    # (o_ref, m, l, acc)
    n_pages: int,
    block_size: int,
    q_len: int,
    group: int,
    window: int,
    softcap: float,
    scale: float,
    quantized: bool = False,
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    hkv = k_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cur = cl_ref[b]
    # Page-level pruning: skip pages entirely past the *youngest* query
    # (cur + q_len - 1; the unallocated tail's table entries point at the
    # trash page) or behind the *oldest* query's window.
    live = j * block_size <= cur + (q_len - 1)
    if window > 0:
        live = live & (cur - (j * block_size + block_size - 1) < window)

    @pl.when(live)
    def _compute():
        rows = q_ref.shape[2]
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 1)
        # Row r is query r // group at absolute position cur + r // group:
        # causal within the draft block, per query.
        qpos = cur + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 0) // group
        ok = pos <= qpos
        if window > 0:
            ok = ok & (qpos - pos < window)
        if quantized:
            ks = ks_ref[0]  # (1, Hkv)
            vs = vs_ref[0]
        for h in range(hkv):  # static: one GQA group per kv head
            q = q_ref[0, h]  # (rows, hd)
            k = k_ref[0, :, h, :]  # (bs, hd)
            v = v_ref[0, :, h, :]
            if quantized:
                k = k.astype(jnp.float32) * ks[:, h:h + 1]
                v = v.astype(jnp.float32) * vs[:, h:h + 1]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if softcap > 0.0:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(ok, s, NEG_INF)

            m_old = m_ref[h]  # (rows, 1)
            m_new = jnp.maximum(m_old, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_old - m_new)
            l_ref[h] = alpha * l_ref[h] + p.sum(axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[h] = alpha * acc_ref[h] + pv
            m_ref[h] = m_new

    @pl.when(j == n_pages - 1)
    def _flush():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def build_specs(b: int, hkv: int, rows: int, hd: int, nb: int, bs: int,
                n_pages: int, *, quantized: bool) -> dict:
    """Grid/BlockSpec layout shared by the kernel call *and* the analyzer's
    kernel lint (``analysis.kernelcheck``).

    The page table and ``cur_len`` are the two scalar-prefetch operands —
    every K/V (and scale) index_map must consume the prefetched table as an
    index (``pt[bb, jj]``), which is exactly what the lint's KRN002 check
    verifies; ``cur_len`` is body-consumed (position masking), so it is not
    listed in ``prefetch_index_operands``.  ``operands``/``out_shape`` are
    the wrapper-declared shapes each BlockSpec tiles (same order as
    ``in_specs``, prefetch excluded).
    """
    in_specs = [
        pl.BlockSpec((1, hkv, rows, hd),
                     lambda bb, jj, pt, cl: (bb, 0, 0, 0)),
        pl.BlockSpec((1, bs, hkv, hd),
                     lambda bb, jj, pt, cl: (pt[bb, jj], 0, 0, 0)),
        pl.BlockSpec((1, bs, hkv, hd),
                     lambda bb, jj, pt, cl: (pt[bb, jj], 0, 0, 0)),
    ]
    operands = [(b, hkv, rows, hd), (nb, bs, hkv, hd), (nb, bs, hkv, hd)]
    if quantized:
        # The scale rides the page's scalar-prefetched index: one (1, 1,
        # Hkv) block of the (num_blocks, 1, Hkv) scale view per grid step.
        in_specs += [
            pl.BlockSpec((1, 1, hkv),
                         lambda bb, jj, pt, cl: (pt[bb, jj], 0, 0)),
            pl.BlockSpec((1, 1, hkv),
                         lambda bb, jj, pt, cl: (pt[bb, jj], 0, 0)),
        ]
        operands += [(nb, 1, hkv), (nb, 1, hkv)]
    return dict(
        grid=(b, n_pages),
        num_scalar_prefetch=2,
        prefetch_index_operands=(0,),  # page table; cur_len is body-read
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, hkv, rows, hd), lambda bb, jj, pt, cl: (bb, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, rows, 1), jnp.float32),
            pltpu.VMEM((hkv, rows, 1), jnp.float32),
            pltpu.VMEM((hkv, rows, hd), jnp.float32),
        ],
        operands=operands,
        out_shape=(b, hkv, rows, hd),
    )


#: Analyzer metadata: lint-time instantiations of ``build_specs`` covering
#: the plain, multi-token (rows = q_len * g) and quantized variants.
KERNEL_META = {
    "paged_attention": dict(
        build=build_specs,
        lint_shapes=dict(b=2, hkv=2, rows=4, hd=8, nb=9, bs=8, n_pages=4,
                         quantized=False),
        grid_dims=("batch", "pages"),
        sequential_dim=1,
    ),
    "paged_attention_multi": dict(
        build=build_specs,
        lint_shapes=dict(b=2, hkv=2, rows=12, hd=8, nb=9, bs=8, n_pages=4,
                         quantized=False),
        grid_dims=("batch", "pages"),
        sequential_dim=1,
    ),
    "paged_attention_quant": dict(
        build=build_specs,
        lint_shapes=dict(b=2, hkv=2, rows=4, hd=8, nb=9, bs=8, n_pages=4,
                         quantized=True),
        grid_dims=("batch", "pages"),
        sequential_dim=1,
    ),
}


def _paged_call(
    qr: jax.Array,  # (B, Hkv, q_len * g, hd)
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_table: jax.Array,
    cur_len: jax.Array,
    *,
    q_len: int,
    group: int,
    window: int,
    softcap: float,
    scale: float,
    interpret: bool,
    k_scale: jax.Array | None = None,  # (num_blocks, Hkv) f32 per-page
    v_scale: jax.Array | None = None,  # per-head scales (quantized pools)
) -> jax.Array:
    b, hkv, rows, hd = qr.shape
    nb, bs, _, _ = k_pool.shape
    n_pages = page_table.shape[1]
    quantized = k_scale is not None
    kern = functools.partial(
        _paged_kernel, n_pages=n_pages, block_size=bs, q_len=q_len,
        group=group, window=window, softcap=softcap, scale=scale,
        quantized=quantized)

    sp = build_specs(b, hkv, rows, hd, nb, bs, n_pages, quantized=quantized)
    inputs = [page_table.astype(jnp.int32), cur_len.astype(jnp.int32), qr,
              k_pool, v_pool]
    if quantized:
        # A free view of the quant.py scale pool whose last two block dims
        # are whole (1, Hkv) — the tiling rule's "equal to the array" case.
        inputs += [k_scale.reshape(nb, 1, hkv), v_scale.reshape(nb, 1, hkv)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=sp["num_scalar_prefetch"],
        grid=sp["grid"],
        in_specs=sp["in_specs"],
        out_specs=sp["out_specs"],
        scratch_shapes=sp["scratch_shapes"],
    )

    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(sp["out_shape"], qr.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*inputs)


def paged_attention_kernel(
    q: jax.Array,  # (B, H, hd) single-token queries (H = Hkv * G)
    k_pool: jax.Array,  # (num_blocks, block_size, Hkv, hd)
    v_pool: jax.Array,  # (num_blocks, block_size, Hkv, hd)
    page_table: jax.Array,  # (B, n_pages) int32
    cur_len: jax.Array,  # (B,) int32
    *,
    window: int = 0,
    softcap: float = 0.0,
    scale: float,
    interpret: bool = False,
    k_scale: jax.Array | None = None,  # (num_blocks, Hkv) f32: quantized
    v_scale: jax.Array | None = None,  # pool scales (dequant fused in)
) -> jax.Array:
    b, h, hd = q.shape
    nb, bs, hkv, _ = k_pool.shape
    assert h % hkv == 0, (h, hkv)
    g = h // hkv
    # Head layout matches _broadcast_kv: query head i attends kv head i // g.
    qr = q.reshape(b, hkv, g, hd)
    out = _paged_call(
        qr, k_pool, v_pool, page_table, cur_len, q_len=1, group=g,
        window=window, softcap=softcap, scale=scale, interpret=interpret,
        k_scale=k_scale, v_scale=v_scale)
    return out.reshape(b, h, hd)


def paged_attention_multi_kernel(
    q: jax.Array,  # (B, T, H, hd): T-token draft block per slot
    k_pool: jax.Array,  # (num_blocks, block_size, Hkv, hd)
    v_pool: jax.Array,  # (num_blocks, block_size, Hkv, hd)
    page_table: jax.Array,  # (B, n_pages) int32
    cur_len: jax.Array,  # (B,) int32: position of token 0 per slot
    *,
    window: int = 0,
    softcap: float = 0.0,
    scale: float,
    interpret: bool = False,
    k_scale: jax.Array | None = None,  # (num_blocks, Hkv) f32: quantized
    v_scale: jax.Array | None = None,  # pool scales (dequant fused in)
) -> jax.Array:
    """q_len>1 decode from the pool: query t of slot b sits at absolute
    position ``cur_len[b] + t`` (speculative verify: one pending token plus
    the draft tail), masked causally within the block."""
    b, t, h, hd = q.shape
    nb, bs, hkv, _ = k_pool.shape
    assert h % hkv == 0, (h, hkv)
    g = h // hkv
    # (B, T, Hkv, g, hd) -> (B, Hkv, T, g, hd): row r = query r // g of
    # group member r % g, matching the kernel's row -> position map.
    qr = q.reshape(b, t, hkv, g, hd).transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, t * g, hd)
    out = _paged_call(
        qr, k_pool, v_pool, page_table, cur_len, q_len=t, group=g,
        window=window, softcap=softcap, scale=scale, interpret=interpret,
        k_scale=k_scale, v_scale=v_scale)
    return out.reshape(b, hkv, t, g, hd).transpose(0, 2, 1, 3, 4).reshape(
        b, t, h, hd)
