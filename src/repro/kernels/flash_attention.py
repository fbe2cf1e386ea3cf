"""Flash attention Pallas kernel: KV streaming with VMEM-resident softmax state.

The TPU-native answer to the reference implementation's dominant memory
roofline term (EXPERIMENTS.md §Perf): the online-softmax state (m, l, acc)
lives in VMEM scratch across the KV stream instead of bouncing through HBM
as a scan carry, and the P matrix never exists in HBM at all.

Grid: (batch*kv_heads*groups, n_q, n_k) — the KV block stream is the
innermost (sequential) dimension so Mosaic pipelines block k+1's DMA against
block k's MXU compute (the paper's stream overlap).  Causal / sliding-window
masking is positional (iota), and fully-masked (qi, kj) pairs skip compute
via ``pl.when`` — matching the block pruning of the reference.

Supports causal, sliding window, logit softcap (gemma2) and GQA via the
caller broadcasting KV (see ops.flash_attention).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # (1, bq, hd)
    k_ref,  # (1, bk, hd)
    v_ref,  # (1, bk, hd)
    o_ref,  # (1, bq, hd)
    m_ref,  # VMEM (bq,)
    l_ref,  # VMEM (bq,)
    acc_ref,  # VMEM (bq, hd)
    *,
    n_k: int,
    block_q: int,
    block_k: int,
    causal: bool,
    window: int,
    softcap: float,
    scale: float,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block-level pruning: skip pairs fully outside the causal triangle or
    # the sliding-window band (the reference impl never schedules them; the
    # rectangular Pallas grid schedules but skips them).
    q_lo = qi * block_q
    q_hi = q_lo + block_q - 1
    k_lo = kj * block_k
    k_hi = k_lo + block_k - 1
    live = jnp.bool_(True)
    if causal:
        live = live & (k_lo <= q_hi)
    if window > 0:
        live = live & (q_lo - k_hi < window)

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)

        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        ok = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            ok = qpos >= kpos
        if window > 0:
            ok = ok & (qpos - kpos < window)
        s = jnp.where(ok, s, NEG_INF)

        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_old - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = alpha[:, None] * acc_ref[...] + pv
        m_ref[...] = m_new

    @pl.when(kj == n_k - 1)
    def _flush():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def build_specs(bh: int, sq: int, sk: int, hd: int, bq: int, bk: int) -> dict:
    """Grid/BlockSpec layout shared by the kernel call *and* the analyzer's
    kernel lint (``analysis.kernelcheck``) — one source of truth, so a spec
    edit that stops matching the operand shapes is caught statically.

    ``operands``/``out_shape`` are the wrapper-declared shapes each
    BlockSpec must tile exactly (same order as ``in_specs``).
    """
    n_q, n_k = sq // bq, sk // bk
    return dict(
        grid=(bh, n_q, n_k),
        num_scalar_prefetch=0,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        operands=[(bh, sq, hd), (bh, sk, hd), (bh, sk, hd)],
        out_shape=(bh, sq, hd),
    )


#: Analyzer metadata: lint-time instantiations of ``build_specs`` (shapes
#: chosen to exercise multi-block grids) and the ops<->ref oracle pair.
KERNEL_META = {
    "flash_attention": dict(
        build=build_specs,
        lint_shapes=dict(bh=2, sq=16, sk=16, hd=8, bq=8, bk=8),
        grid_dims=("batch_heads", "q_blocks", "k_blocks"),
        sequential_dim=2,
    ),
}


def flash_attention_kernel(
    q: jax.Array,  # (BH, Sq, hd)  (batch*heads flattened; KV pre-broadcast)
    k: jax.Array,  # (BH, Sk, hd)
    v: jax.Array,  # (BH, Sk, hd)
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    scale: float,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    bh, sq, hd = q.shape
    sk = k.shape[1]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, sk, bq, bk)
    sp = build_specs(bh, sq, sk, hd, bq, bk)
    n_k = sp["grid"][2]

    kern = functools.partial(
        _flash_kernel, n_k=n_k, block_q=bq, block_k=bk, causal=causal,
        window=window, softcap=softcap, scale=scale)

    return pl.pallas_call(
        kern,
        grid=sp["grid"],
        in_specs=sp["in_specs"],
        out_specs=sp["out_specs"],
        out_shape=jax.ShapeDtypeStruct(sp["out_shape"], q.dtype),
        scratch_shapes=sp["scratch_shapes"],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
