"""Cluster-level streaming: collective <-> compute overlap.

At pod scale the "transfer" stage of the paper's pipeline is the collective.
A blocking ``all-gather -> matmul`` serializes the two stages exactly like the
paper's single-stream baseline; the ring **collective matmul** decomposes the
gather into P-1 ``ppermute`` hops and overlaps each hop with a chunk matmul --
the multi-stream pipeline, expressed in ``shard_map``.

Both the blocking reference and the ring version are provided; the model's
linear layers select via ``use_collective_matmul``.  The dry-run roofline
distinguishes the two in HLO: all-gather/all-reduce bytes (blocking) vs
collective-permute bytes (overlappable), and the §Perf log uses exactly this
lever on the collective-bound cells.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# ----------------------------------------------------------------------------
# Blocking references (single-stream analogue).
# ----------------------------------------------------------------------------


def ag_matmul_reference(x: jax.Array, w: jax.Array, axis_name: str) -> jax.Array:
    """y_local = all_gather(x) @ w_local -- transfer then compute (blocking).

    ``x``: (m_local, k) sharded over ``axis_name`` on rows.
    ``w``: (k, n_local) sharded on columns.
    Returns (m_full, n_local).
    """
    x_full = jax.lax.all_gather(x, axis_name, axis=0, tiled=True)
    return x_full @ w


def rs_matmul_reference(x: jax.Array, w: jax.Array, axis_name: str) -> jax.Array:
    """y_local = reduce_scatter(x @ w_local_k) -- compute then transfer.

    ``x``: (m_full, k_local); ``w``: (k_local, n).  The partial products are
    summed across the axis and the result's rows scattered:
    returns (m_full / P, n).
    """
    partial = x @ w  # (m_full, n), partial sum over k shards
    return jax.lax.psum_scatter(partial, axis_name, scatter_dimension=0, tiled=True)


# ----------------------------------------------------------------------------
# Ring (streamed) versions: ppermute hops overlap chunk matmuls.
# ----------------------------------------------------------------------------


def ag_matmul_ring(x: jax.Array, w: jax.Array, axis_name: str) -> jax.Array:
    """Streamed all-gather matmul.

    Each of the P steps multiplies the currently-held x shard into its row
    block of the output while the next shard is in flight on the ring
    (``ppermute``).  Same math as ``ag_matmul_reference``; the collective is
    decomposed into P-1 overlappable hops.
    """
    p = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    m_local = x.shape[0]
    y = jnp.zeros((m_local * p, w.shape[1]), dtype=jnp.result_type(x.dtype, w.dtype))
    # The accumulator is device-varying (each device fills different rows).
    y = jax.lax.pcast(y, (axis_name,), to="varying")
    perm = [(i, (i - 1) % p) for i in range(p)]  # send to the left neighbour

    def step(i, carry):
        y, x_cur = carry
        # The shard now held originated at device (idx + i) mod p.
        src = (idx + i) % p
        y = jax.lax.dynamic_update_slice(y, (x_cur @ w).astype(y.dtype), (src * m_local, 0))
        # Kick off the next hop; on TPU this DMA overlaps the next matmul.
        x_nxt = jax.lax.ppermute(x_cur, axis_name, perm)
        return y, x_nxt

    y, _ = jax.lax.fori_loop(0, p, step, (y, x))
    return y


def rs_matmul_ring(x: jax.Array, w: jax.Array, axis_name: str) -> jax.Array:
    """Streamed reduce-scatter matmul.

    Step i computes the partial product destined for the neighbour that is i
    hops away and adds it to an accumulator circulating on the ring; after P
    steps every device holds the fully-reduced rows it owns.  The accumulator
    hop overlaps the next chunk's matmul.
    """
    p = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    m_full = x.shape[0]
    assert m_full % p == 0, "rows must divide the axis size"
    m_local = m_full // p
    perm = [(i, (i + 1) % p) for i in range(p)]  # pass accumulator right

    def chunk(j):
        # Partial product for the row-block owned by device (idx - j) mod p.
        owner = (idx - j) % p
        xs = jax.lax.dynamic_slice(x, (owner * m_local, 0), (m_local, x.shape[1]))
        return xs @ w

    # The accumulator for owner (idx-1) starts here, then hops right, picking
    # up one partial per device; after p-1 hops it reaches its owner.  At step
    # i the accumulator now held here is the one for owner (idx - i - 2).
    acc = chunk(1)

    def step(i, acc):
        acc = jax.lax.ppermute(acc, axis_name, perm)
        return acc + chunk(i + 2)

    acc = jax.lax.fori_loop(0, p - 1, step, acc)
    return acc


# ----------------------------------------------------------------------------
# shard_map wrappers for direct use outside model code.
# ----------------------------------------------------------------------------


def make_sharded_ag_matmul(
    mesh: jax.sharding.Mesh, axis_name: str, *, ring: bool = True
) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """Build y = X @ W with X row-sharded and W col-sharded over ``axis_name``."""
    fn = ag_matmul_ring if ring else ag_matmul_reference

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis_name, None), P(None, axis_name)),
        out_specs=P(None, axis_name),
    )
    def _run(x, w):
        return fn(x, w, axis_name)

    return _run


def make_sharded_rs_matmul(
    mesh: jax.sharding.Mesh, axis_name: str, *, ring: bool = True
) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """Build y = reduce_scatter(X @ W) with W row-sharded over ``axis_name``."""
    fn = rs_matmul_ring if ring else rs_matmul_reference

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(None, axis_name), P(axis_name, None)),
        out_specs=P(axis_name, None),
    )
    def _run(x, w):
        return fn(x, w, axis_name)

    return _run
