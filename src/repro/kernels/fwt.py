"""Fast Walsh-Hadamard Transform kernel — the paper's False-Dependent case study.

The paper streams FWT by splitting the input into blocks and transferring
the (read-only) boundary elements redundantly with each block (§4.2, Fig 7).
On TPU the same decomposition is the Kronecker factorization

    WHT(N) = (WHT(B1) ⊗ I) · (I ⊗ WHT(B2)),   N = B1 * B2:

each kernel invocation transforms an independent length-``block`` segment
(in-block butterfly stages run entirely in VMEM), and the cross-block stages
become a second streamed pass over the transposed layout — the "redundant
boundary transfer" of the paper becomes a transpose between two clean
streams, which is the TPU-idiomatic way to eliminate the RAR dependency.

The grid dimension is the stream: block i+1's DMA overlaps block i's
butterflies.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fwt_block_kernel(x_ref, o_ref, *, block: int):
    """In-VMEM WHT over the last axis of a (rows, block) tile."""
    x = x_ref[...].astype(jnp.float32)
    h = 1
    while h < block:
        # butterfly stage with stride h over the last axis
        x = x.reshape(x.shape[0], block // (2 * h), 2, h)
        a = x[:, :, 0, :]
        b = x[:, :, 1, :]
        x = jnp.stack([a + b, a - b], axis=2)
        x = x.reshape(x.shape[0], block)
        h *= 2
    o_ref[...] = x.astype(o_ref.dtype)


def fwt_block(
    x: jax.Array,  # (n_rows, block): independent segments (tasks)
    *,
    row_tile: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Walsh-Hadamard transform of each row, streamed over row tiles."""
    n_rows, block = x.shape
    assert block & (block - 1) == 0, f"block {block} must be a power of two"
    rt = min(row_tile, n_rows)
    assert n_rows % rt == 0, (n_rows, rt)

    return pl.pallas_call(
        functools.partial(_fwt_block_kernel, block=block),
        grid=(n_rows // rt,),
        in_specs=[pl.BlockSpec((rt, block), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rt, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows, block), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(x)
