"""Production mesh construction (FUNCTION, not module constant -- importing
this module never touches jax device state).

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the "pod" axis carries
data parallelism across the inter-pod (DCN-class) links and is the axis the
optional pipeline-parallel mode stages over.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, which the logical-axis
    hooks (``with_sharding_constraint`` on a bare ``PartitionSpec``) and the
    ``shard_map``-based @sharded routes reject; every mesh of this package
    is built here so that all of them use the sharding-in-types-free mode.
    """
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None, model: int = 2):
    """Small mesh over however many (fake) devices exist -- tests only."""
    n = n_devices or len(jax.devices())
    data = n // model
    return make_mesh((data, model), ("data", "model"))
