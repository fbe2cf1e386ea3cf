"""Production mesh construction.

Single pod: (data=16, model=16) over 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) over 512 chips; the ``pod`` axis is
the DCN dimension -- batch (and gradient all-reduce) shard over it, while
parameters stay within-pod (FSDP over ``data``, TP over ``model``) so no
per-layer weight gather ever crosses the slow inter-pod links.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module does not touch jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to build the placeholder devices.

Every mesh is built with Auto axes: the sharding rules in
``launch/sharding.py`` give the inputs' layouts and leave the rest to XLA's
propagation.  Install one with ``jax.set_mesh(mesh)`` so the activation
hints in ``models/meshutil`` see it.
"""

from __future__ import annotations

import jax


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...],
              devices=None) -> jax.sharding.Mesh:
    """A mesh of Auto axes over ``devices`` (default: all of them)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(*, data: int | None = None, model: int = 1,
                   devices=None) -> jax.sharding.Mesh:
    """Small (data, model) mesh over ``devices`` (default: all of them)."""
    n = len(devices) if devices is not None else jax.device_count()
    data = data if data is not None else max(1, n // model)
    return make_mesh((data, model), ("data", "model"), devices=devices)


def axis_sizes(mesh: jax.sharding.Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
