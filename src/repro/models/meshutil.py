"""Activation sharding hints.

XLA's sharding propagation can settle on a TP-style layout (batch
replicated, embed dim sharded) when the embedding table's sharding wins the
propagation war through the scan carry.  These helpers pin activations to
batch-sharded layout at layer boundaries -- no-ops when no mesh is active
(CPU smoke tests) or when a dim doesn't divide the axis.
"""

from __future__ import annotations

import math

import jax
from jax.sharding import PartitionSpec as P


def current_mesh():
    """The mesh installed by ``jax.set_mesh(mesh)``, or None."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def batch_axes_for(b: int, sizes: dict[str, int]):
    if "pod" in sizes and "data" in sizes:
        if b % (sizes["pod"] * sizes["data"]) == 0:
            return ("pod", "data")
    if "data" in sizes and b % sizes["data"] == 0:
        return ("data",)
    return None


def shard_batch(x: jax.Array) -> jax.Array:
    """Constrain the leading (batch) dim over the data axes."""
    mesh = current_mesh()
    if mesh is None:
        return x
    sizes = dict(mesh.shape)
    ax = batch_axes_for(x.shape[0], sizes)
    return jax.lax.with_sharding_constraint(
        x, P(ax, *([None] * (x.ndim - 1))))


def shard_spec(x: jax.Array, *axes) -> jax.Array:
    """Constrain with the given axes, dropping non-dividing/missing ones."""
    mesh = current_mesh()
    if mesh is None:
        return x
    sizes = dict(mesh.shape)

    def ok(a, dim):
        if a is None:
            return None
        if isinstance(a, tuple):
            prod = math.prod(sizes.get(x_, 0) or 1 for x_ in a)
            return a if all(x_ in sizes for x_ in a) and dim % prod == 0 else None
        return a if a in sizes and dim % sizes[a] == 0 else None

    spec = [ok(a, d) for a, d in zip(axes, x.shape)]
    return jax.lax.with_sharding_constraint(x, P(*spec))
