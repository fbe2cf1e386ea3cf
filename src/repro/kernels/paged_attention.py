"""Paged decode-attention Pallas kernel: block-wise attention from the pool.

The TPU twin of ``repro.models.attention.paged_decode_attention``: instead
of gathering a slot's pages into a contiguous (B, S, Hkv, hd) view in HBM,
the kernel copies each row's live pages straight from the pool into VMEM,
through the scalar-prefetched page table.

Grid: (batch, n_blocks), with n_blocks = ceil(n_pages / P), walked in row
order. A compute block is P logical pages of one row, P * block_size keys.
P follows from the shapes (:func:`pages_per_block`: ``KEYS_PER_BLOCK``
keys, clamped to [1, n_pages]), so the last block may be partial.

DMA: the K and V pools stay in HBM (``memory_space=pl.ANY``). They are the
model's stacked pools ``(layers, num_blocks, block_size, Hkv, hd)``, and a
scalar-prefetched layer index picks the layer, so a decode step hands every
attention layer the same buffers and no layer's pool is ever sliced out.
Each block's pages are copied, one async copy per page at ``[layer,
physical id]``, into one half of a double buffer ``(2, P, block_size, Hkv,
hd)``. Only live pages are copied: a page is live for a row when it holds a
position at or below the row's youngest query (``cur_len + q_len - 1``),
inside the oldest query's window when ``window > 0``, and lies inside the
table. A row's live pages are one run, so a block's are a range of its
slots, and a row's live blocks are consecutive. Each live step starts the
copies of the next live block in row order into the other half (the same
row's next block, or after the row's last live block the next row's first),
then waits for its own and computes. A block with no live page issues no
copy and no compute, so a dead tail costs only empty grid steps. The V
slots of a live block that were not copied are zeroed, so stale VMEM never
meets a zero softmax weight. A quantized pool's (num_blocks, Hkv) f32
scales of the layer sit whole in VMEM; a page's row of scales is copied
beside the page, by the same physical id.

Compute: all KV heads at once. The queries of every head, ``Hkv * q_len *
g`` rows, are scored against the whole block viewed as ``(P * block_size *
Hkv, hd)`` (key-major, heads minor) in one product, and a score counts only
where the key's head is the row's head. One PV product over the same view
follows. A head's keys are thus read with no strided access, at the price
of Hkv times the MXU work, which is cheap beside the copies. Each row keeps
its online-softmax state (m, l, acc) in f32 VMEM scratch across the block
stream. Masking is positional (iota vs ``cur_len``, and the table's end
where the last block runs past it), so pages that were not copied, the
trash page and positions past a row's end never contribute. A
quantized pool's codes enter the products unscaled; each key's scale
multiplies its score, and each value's the weight it is summed with.

``q_len > 1`` (speculative multi-token decode) folds the query block into
the rows: row ``r`` of a head is query ``r // g``, at absolute position
``cur_len + r // g``, the causal-within-the-block mask of the verify step. A
page is dead only when *every* query masks it (the youngest query bounds
the causal cut, the oldest bounds the window cut).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: Keys of one compute block; P = this // block_size pages.
KEYS_PER_BLOCK = 512


def pages_per_block(block_size: int, n_pages: int) -> int:
    """P, the pages of one compute block: ``KEYS_PER_BLOCK`` keys' worth,
    at least one page and at most the whole table."""
    return max(1, min(KEYS_PER_BLOCK // block_size, n_pages))


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _paged_kernel(
    pt_ref,  # SMEM (B, n_pages) int32: scalar-prefetched page table
    cl_ref,  # SMEM (B,) int32: per-row current position
    ly_ref,  # SMEM (1,) int32: the layer of the stacked pools
    q_ref,  # (1, Hkv * q_len * g, hd): head-major rows
    k_hbm,  # (layers, num_blocks, bs, Hkv, hd) pools, left in HBM
    v_hbm,
    *rest,  # quantized: (ks_ref, vs_ref, o_ref, m, l, acc, kbuf, vbuf,
    # ksbuf, vsbuf, par, sem), the layer's scale pools (num_blocks, Hkv)
    # whole in VMEM and their per-page rows (2, P, 1, Hkv); else (o_ref, m,
    # l, acc, kbuf, vbuf, par, sem)
    n_pages: int,
    pages: int,
    q_len: int,
    group: int,
    window: int,
    softcap: float,
    scale: float,
    quantized: bool = False,
):
    if quantized:
        (ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref, kbuf, vbuf, ksbuf,
         vsbuf, par_ref, sem) = rest
    else:
        o_ref, m_ref, l_ref, acc_ref, kbuf, vbuf, par_ref, sem = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_rows = pl.num_programs(0)
    _, _, bs, hkv, hd = kbuf.shape

    def live_pages(row):
        """The row's live pages [first, end): from the oldest query's window
        (all of them without one) through the youngest query's position,
        cut at the table's end (a draft block's padding tail runs past it)."""
        cur = cl_ref[row]
        first = jnp.maximum(cur - window + 1, 0) // bs if window > 0 else 0
        end = jnp.minimum((cur + q_len - 1) // bs + 1, n_pages)
        return cur, first, end

    def live(blk, first, end):
        """Slots [lo, hi) of block ``blk`` that hold live pages."""
        base = blk * pages
        return (jnp.clip(first - base, 0, pages),
                jnp.clip(end - base, 0, pages))

    def copies(row, blk, slot, i):
        pid = pt_ref[row, blk * pages + i]
        return pid, [pltpu.make_async_copy(src.at[ly_ref[0], pid],
                                           dst.at[slot, i], sem.at[slot])
                     for src, dst in ((k_hbm, kbuf), (v_hbm, vbuf))]

    def fetch(row, blk, first, end, slot):
        def start(i, carry):
            pid, cps = copies(row, blk, slot, i)
            for cp in cps:
                cp.start()
            if quantized:
                ksbuf[slot, i] = ks_ref[pl.ds(pid, 1), :]
                vsbuf[slot, i] = vs_ref[pl.ds(pid, 1), :]
            return carry
        jax.lax.fori_loop(*live(blk, first, end), start, 0)

    cur, first, end = live_pages(b)
    last = (end - 1) // pages  # the row's last live block

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block j of this row sits in buffer half (j + par) % 2; par is chosen
    # as each row's first live block is fetched, so that it lands in the
    # half the previous row's last live block does not use.
    @pl.when((b == 0) & (j == first // pages))
    def _first():
        par_ref[0] = 0
        fetch(b, j, first, end, j % 2)

    lo, hi = live(j, first, end)

    @pl.when(lo < hi)
    def _step():
        slot = (j + par_ref[0]) % 2

        @pl.when(j < last)
        def _next_block():
            fetch(b, j + 1, first, end, 1 - slot)

        @pl.when((j == last) & (b + 1 < n_rows))
        def _next_row():
            _, first1, end1 = live_pages(b + 1)
            j1 = first1 // pages
            fetch(b + 1, j1, first1, end1, 1 - slot)
            par_ref[0] = (1 - slot + j1) % 2

        def wait(i, carry):
            for cp in copies(b, j, slot, i)[1]:
                cp.wait()
            return carry
        jax.lax.fori_loop(lo, hi, wait, 0)

        def zero(i, carry):
            vbuf[slot, i] = jnp.zeros(vbuf.shape[2:], vbuf.dtype)
            if quantized:
                vsbuf[slot, i] = jnp.zeros(vsbuf.shape[2:], vsbuf.dtype)
            return carry
        jax.lax.fori_loop(0, lo, zero, 0)
        jax.lax.fori_loop(hi, pages, zero, 0)

        rows = q_ref.shape[1]  # Hkv * q_len * g
        cols = pages * bs * hkv  # key-major, heads minor
        col = _iota((rows, cols), 1)
        row = _iota((rows, cols), 0)
        per_head = rows // hkv
        pos = j * pages * bs + col // hkv
        # Row r is query (r % per_head) // group of head r // per_head, at
        # absolute position cur + that query: causal within a draft block.
        qpos = cur + (row % per_head) // group
        ok = (col % hkv == row // per_head) & (pos <= qpos)
        if window > 0:
            ok = ok & (qpos - pos < window)
        if n_pages % pages:  # the last block runs past the table
            ok = ok & (pos < n_pages * bs)
        q = q_ref[0]  # (rows, hd)
        k = kbuf[slot].reshape(cols, hd)
        v = vbuf[slot].reshape(cols, hd)
        if quantized:
            k = k.astype(q.dtype)  # int8 and e4m3 codes are exact in bf16
            v = v.astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if quantized:
            s = s * _col_scales(ksbuf[slot], bs)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(ok, s, NEG_INF)

        m_old = m_ref[...]  # (rows, 1)
        m_new = jnp.maximum(m_old, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_old - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        if quantized:
            p = p * _col_scales(vsbuf[slot], bs)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = alpha * acc_ref[...] + pv
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _col_scales(sc: jax.Array, bs: int) -> jax.Array:
    """(P, 1, Hkv) per-page scales -> (1, P * bs * Hkv): the scale of each
    column of the block's key-major, heads-minor view."""
    pages, _, hkv = sc.shape
    sc = sc.reshape(pages, hkv)
    shape = (pages, pages * bs * hkv)
    col = _iota(shape, 1)
    by_head = jnp.zeros(shape, jnp.float32)
    for h in range(hkv):
        by_head = jnp.where(col % hkv == h, sc[:, h:h + 1], by_head)
    own_page = col // (bs * hkv) == _iota(shape, 0)
    return jnp.where(own_page, by_head, 0.0).sum(axis=0, keepdims=True)


def build_specs(b: int, hkv: int, rows: int, hd: int, nb: int, bs: int,
                n_pages: int, *, quantized: bool, layers: int = 1,
                pool_dtype=jnp.bfloat16) -> dict:
    """Grid/BlockSpec layout shared by the kernel call *and* the analyzer's
    kernel lint (``analysis.kernelcheck``). ``rows`` is the query rows per
    KV head (``q_len * g``); ``layers`` is the stacked pools' leading axis.

    The page table, ``cur_len`` and the layer index are the three
    scalar-prefetch operands. The queries and the output are blocked per
    batch row. The pools are HBM-resident (``pl.ANY``; ``hbm_operands``
    names them and the rank the body's copies index, ``[layer, page]``),
    so no index_map reads the page table: the body's DMAs do, which
    ``KERNEL_META``'s ``dma_index_operands`` declares. ``cur_len`` is
    body-consumed (position masking). ``operands`` / ``out_shape`` are the
    wrapper-declared shapes (same order as ``in_specs``, prefetch
    excluded).
    """
    pages = pages_per_block(bs, n_pages)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    qo = pl.BlockSpec((1, hkv * rows, hd),
                      lambda bb, jj, pt, cl, ly: (bb, 0, 0))
    in_specs = [qo, hbm, hbm]
    pool = (layers, nb, bs, hkv, hd)
    operands = [(b, hkv * rows, hd), pool, pool]
    scratch = [
        pltpu.VMEM((hkv * rows, 1), jnp.float32),
        pltpu.VMEM((hkv * rows, 1), jnp.float32),
        pltpu.VMEM((hkv * rows, hd), jnp.float32),
        pltpu.VMEM((2, pages, bs, hkv, hd), pool_dtype),
        pltpu.VMEM((2, pages, bs, hkv, hd), pool_dtype),
    ]
    if quantized:
        # The layer's (num_blocks, Hkv) scale pools are small (4 B a page
        # and head): one whole block each, fetched once per call.
        in_specs += [pl.BlockSpec((None, nb, hkv),
                                  lambda bb, jj, pt, cl, ly: (ly[0], 0, 0))
                     ] * 2
        operands += [(layers, nb, hkv), (layers, nb, hkv)]
        scratch += [pltpu.VMEM((2, pages, 1, hkv), jnp.float32)] * 2
    return dict(
        grid=(b, pl.cdiv(n_pages, pages)),
        pages=pages,
        num_scalar_prefetch=3,
        # The page table and the layer; cur_len is body-read.
        prefetch_index_operands=(0, 2),
        in_specs=in_specs,
        hbm_operands={1: 5, 2: 5},
        out_specs=qo,
        scratch_shapes=scratch + [pltpu.SMEM((1,), jnp.int32),
                                  pltpu.SemaphoreType.DMA((2,))],
        operands=operands,
        out_shape=(b, hkv * rows, hd),
    )


#: Analyzer metadata: lint-time instantiations of ``build_specs`` covering
#: the plain, multi-token (rows = q_len * g) and quantized variants.
_META = dict(build=build_specs, grid_dims=("batch", "page_blocks"),
             dma_index_operands=(0, 2))
KERNEL_META = {
    "paged_attention": dict(
        _META, lint_shapes=dict(b=2, hkv=2, rows=4, hd=8, nb=9, bs=8,
                                n_pages=4, quantized=False, layers=3)),
    "paged_attention_multi": dict(
        _META, lint_shapes=dict(b=2, hkv=2, rows=12, hd=8, nb=9, bs=8,
                                n_pages=4, quantized=False, layers=3)),
    "paged_attention_quant": dict(
        _META, lint_shapes=dict(b=2, hkv=2, rows=4, hd=8, nb=9, bs=8,
                                n_pages=4, quantized=True, layers=3)),
}


def _stacked(k_pool, v_pool, k_scale, v_scale, layer):
    """The pools as ``(layers, ...)`` stacks and the layer index as the
    (1,) int32 prefetch operand; one layer's pools (no ``layer``) are a
    stack of one, a free reshape."""
    if layer is None:
        return (k_pool[None], v_pool[None],
                None if k_scale is None else k_scale[None],
                None if v_scale is None else v_scale[None],
                jnp.zeros((1,), jnp.int32))
    return (k_pool, v_pool, k_scale, v_scale,
            jnp.asarray(layer, jnp.int32).reshape(1))


def _paged_call(
    qr: jax.Array,  # (B, Hkv, q_len * g, hd)
    k_pool: jax.Array,  # (layers, num_blocks, bs, Hkv, hd)
    v_pool: jax.Array,
    page_table: jax.Array,
    cur_len: jax.Array,
    layer: jax.Array,  # (1,) int32
    *,
    q_len: int,
    group: int,
    window: int,
    softcap: float,
    scale: float,
    interpret: bool,
    k_scale: jax.Array | None = None,  # (layers, num_blocks, Hkv) f32
    v_scale: jax.Array | None = None,  # per-page, per-head scales
) -> jax.Array:
    b, hkv, rows, hd = qr.shape
    n_layers, nb, bs, _, _ = k_pool.shape
    n_pages = page_table.shape[1]
    quantized = k_scale is not None
    sp = build_specs(b, hkv, rows, hd, nb, bs, n_pages, quantized=quantized,
                     layers=n_layers, pool_dtype=k_pool.dtype)
    kern = functools.partial(
        _paged_kernel, n_pages=n_pages, pages=sp["pages"], q_len=q_len,
        group=group, window=window, softcap=softcap, scale=scale,
        quantized=quantized)

    inputs = [page_table.astype(jnp.int32), cur_len.astype(jnp.int32),
              layer, qr.reshape(b, hkv * rows, hd), k_pool, v_pool]
    if quantized:
        inputs += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=sp["num_scalar_prefetch"],
        grid=sp["grid"],
        in_specs=sp["in_specs"],
        out_specs=sp["out_specs"],
        scratch_shapes=sp["scratch_shapes"],
    )

    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(sp["out_shape"], qr.dtype),
        # Rows run in order: a row's last live step starts the next row's
        # first copies.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(*inputs)
    return out.reshape(b, hkv, rows, hd)


def paged_attention_kernel(
    q: jax.Array,  # (B, H, hd) single-token queries (H = Hkv * G)
    k_pool: jax.Array,  # (num_blocks, block_size, Hkv, hd), or stacked
    v_pool: jax.Array,  # (layers, num_blocks, ...) with ``layer``
    page_table: jax.Array,  # (B, n_pages) int32
    cur_len: jax.Array,  # (B,) int32
    *,
    window: int = 0,
    softcap: float = 0.0,
    scale: float,
    interpret: bool = False,
    k_scale: jax.Array | None = None,  # ([layers,] num_blocks, Hkv) f32:
    v_scale: jax.Array | None = None,  # quantized pool scales (fused)
    layer: jax.Array | int | None = None,  # int32 index into stacked pools
) -> jax.Array:
    b, h, hd = q.shape
    hkv = k_pool.shape[-2]
    assert h % hkv == 0, (h, hkv)
    g = h // hkv
    # Head layout matches _broadcast_kv: query head i attends kv head i // g.
    qr = q.reshape(b, hkv, g, hd)
    k_pool, v_pool, k_scale, v_scale, ly = _stacked(
        k_pool, v_pool, k_scale, v_scale, layer)
    out = _paged_call(
        qr, k_pool, v_pool, page_table, cur_len, ly, q_len=1, group=g,
        window=window, softcap=softcap, scale=scale, interpret=interpret,
        k_scale=k_scale, v_scale=v_scale)
    return out.reshape(b, h, hd)


def paged_attention_multi_kernel(
    q: jax.Array,  # (B, T, H, hd): T-token draft block per slot
    k_pool: jax.Array,  # (num_blocks, block_size, Hkv, hd), or stacked
    v_pool: jax.Array,  # (layers, num_blocks, ...) with ``layer``
    page_table: jax.Array,  # (B, n_pages) int32
    cur_len: jax.Array,  # (B,) int32: position of token 0 per slot
    *,
    window: int = 0,
    softcap: float = 0.0,
    scale: float,
    interpret: bool = False,
    k_scale: jax.Array | None = None,  # ([layers,] num_blocks, Hkv) f32:
    v_scale: jax.Array | None = None,  # quantized pool scales (fused)
    layer: jax.Array | int | None = None,  # int32 index into stacked pools
) -> jax.Array:
    """q_len>1 decode from the pool: query t of slot b sits at absolute
    position ``cur_len[b] + t`` (speculative verify: one pending token plus
    the draft tail), masked causally within the block."""
    b, t, h, hd = q.shape
    hkv = k_pool.shape[-2]
    assert h % hkv == 0, (h, hkv)
    g = h // hkv
    # (B, T, Hkv, g, hd) -> (B, Hkv, T, g, hd): row r = query r // g of
    # group member r % g, matching the kernel's row -> position map.
    qr = q.reshape(b, t, hkv, g, hd).transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, t * g, hd)
    k_pool, v_pool, k_scale, v_scale, ly = _stacked(
        k_pool, v_pool, k_scale, v_scale, layer)
    out = _paged_call(
        qr, k_pool, v_pool, page_table, cur_len, ly, q_len=t, group=g,
        window=window, softcap=softcap, scale=scale, interpret=interpret,
        k_scale=k_scale, v_scale=v_scale)
    return out.reshape(b, hkv, t, g, hd).transpose(0, 2, 1, 3, 4).reshape(
        b, t, h, hd)
