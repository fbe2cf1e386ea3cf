"""Mamba2/SSD chunk-scan kernel: True-dependent streaming inside one kernel.

The roofline table (EXPERIMENTS.md) shows the mamba2 cells memory-bound,
dominated by the f32 inter-chunk state round-tripping through HBM as a scan
carry.  This kernel keeps the (N, P) SSM state in VMEM scratch across the
chunk stream: grid = (batch*heads, n_chunks) with the chunk dimension
sequential — chunk t+1's input DMA overlaps chunk t's MXU work, and the
state handoff (the paper's RAW dependency between tasks) never leaves VMEM.

Math identical to ``repro.models.mamba.ssd_chunked`` (the oracle):

    y[t] = (tril(C B^T ∘ L)) X_dt  +  exp(cs) C state_in
    state_out = exp(cs[-1]) state_in + B^T (exp(cs[-1]-cs) ∘ X_dt)

The in-chunk cumulative log-decay is computed with a log-step shift ladder
(no 1-D cumsum primitive needed on the VPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _cumsum_ladder(v: jax.Array, q: int) -> jax.Array:
    """Inclusive prefix sum over a (Q,) vector via log2(Q) shifted adds."""
    x = v
    shift = 1
    while shift < q:
        x = x + jnp.concatenate([jnp.zeros((shift,), x.dtype), x[:-shift]])
        shift *= 2
    return x


def _ssd_kernel(
    xdt_ref,  # (1, Q, P)  dt-weighted inputs for this (bh, chunk)
    adt_ref,  # (1, Q)     dt * a  (negative log-decays)
    b_ref,  # (1, Q, N)
    c_ref,  # (1, Q, N)
    y_ref,  # out (1, Q, P)
    state_ref,  # VMEM scratch (N, P), persists across the chunk stream
    *,
    n_chunks: int,
    q: int,
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _reset():  # new (batch, head): fresh state
        state_ref[...] = jnp.zeros_like(state_ref)

    xdt = xdt_ref[0].astype(jnp.float32)  # (Q, P)
    adt = adt_ref[0].astype(jnp.float32)  # (Q,)
    bq = b_ref[0].astype(jnp.float32)  # (Q, N)
    cq = c_ref[0].astype(jnp.float32)

    cs = _cumsum_ladder(adt, q)  # (Q,) cumulative log-decay
    # intra-chunk decay matrix L[i, j] = exp(cs_i - cs_j) for i >= j
    ldiff = cs[:, None] - cs[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    l = jnp.exp(jnp.where(ii >= jj, ldiff, NEG))

    scores = jax.lax.dot_general(  # C B^T: (Q, Q)
        cq, bq, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    y_diag = jax.lax.dot_general(  # (scores ∘ L) X: (Q, P)
        scores * l, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    state = state_ref[...]  # (N, P)
    y_off = jax.lax.dot_general(  # C state: (Q, P)
        cq, state, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y_off = y_off * jnp.exp(cs)[:, None]

    # state update: decay to chunk end, inject chunk inputs
    decay_to_end = jnp.exp(cs[-1] - cs)  # (Q,)
    chunk_state = jax.lax.dot_general(  # B^T (decay ∘ X): (N, P)
        bq, xdt * decay_to_end[:, None], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    state_ref[...] = state * jnp.exp(cs[-1]) + chunk_state

    y_ref[0] = (y_diag + y_off).astype(y_ref.dtype)


def ssd_chunk_kernel(
    xdt: jax.Array,  # (BH, S, P) dt-weighted inputs
    adt: jax.Array,  # (BH, S) dt * a
    b_: jax.Array,  # (BH, S, N)
    c_: jax.Array,  # (BH, S, N)
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> jax.Array:
    """Returns y (BH, S, P). State stays in VMEM across the chunk stream."""
    bh, s, p = xdt.shape
    n = b_.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    n_chunks = s // chunk

    kern = functools.partial(_ssd_kernel, n_chunks=n_chunks, q=chunk)
    return pl.pallas_call(
        kern,
        grid=(bh, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, chunk), lambda b, t: (b, t)),
            pl.BlockSpec((1, chunk, n), lambda b, t: (b, t, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, t: (b, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda b, t: (b, t, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, p), xdt.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xdt, adt, b_, c_)
