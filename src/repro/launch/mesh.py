"""Production mesh definitions (functions, never module-level constants —
importing this module must not touch jax device state)."""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``: the runtime
    lays arrays out with NamedShardings and sharding constraints and lets
    the compiler propagate the rest."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi-pod adds the 2-pod DCN axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    return make_mesh(shape, axes)


def make_pod_mesh(n_pods: int, axis: str = "pod"):
    """1-D mesh for the sharded FlatParams bus (core/flat.py
    ShardedTreeSpec): each of the ``n_pods`` devices owns one contiguous
    BLOCK-padded segment of the flat buffer, so the flat kernels run
    per-shard under shard_map with no gather (runtime/sharding.py)."""
    if n_pods < 1:
        raise ValueError(f"n_pods must be >= 1, got {n_pods}")
    return make_mesh((n_pods,), (axis,))
