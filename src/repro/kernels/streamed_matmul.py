"""Streamed (multi-buffered) matmul kernel — the paper's pipeline on TPU.

The grid + BlockSpec index maps below ARE the multiple-stream mechanism at
the chip level: Mosaic turns the sequential (i, j, k) task grid into an
HBM->VMEM DMA pipeline where block (i, j, k+1)'s transfer overlaps block
(i, j, k)'s MXU compute — exactly the paper's "H2D of task t+1 overlaps KEX
of task t" (level L2).

Block shapes are chosen so the working set (x-block + y-block + f32
accumulator) fits VMEM and the MXU dims are multiples of 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mm_kernel(x_ref, y_ref, o_ref, acc_ref, *, nk: int):
    """One (bm x bk) @ (bk x bn) task; accumulates over the k stream."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], y_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def streamed_matmul(
    x: jax.Array,  # (m, k)
    y: jax.Array,  # (k, n)
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """y = x @ y with an explicit streaming task grid.

    VMEM budget: bm*bk + bk*bn (input dtype) + bm*bn*4 (f32 acc); defaults
    (256, 256, 512) use 256*512*2*2 + 256*256*4 = 0.8 MiB — comfortably
    double-bufferable within the ~64 MiB/core VMEM budget.
    """
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)

    return pl.pallas_call(
        functools.partial(_mm_kernel, nk=k // bk),
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.result_type(x.dtype, y.dtype)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, y)
