"""Production mesh definition (per assignment spec).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _axis_kw(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kw(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / examples / elastic restore)."""
    return jax.make_mesh(tuple(shape), tuple(axes), **_axis_kw(len(axes)))


def mesh_shardings(mesh):
    """(row_sharded, replicated) ``NamedSharding`` pair over a 1-D mesh.

    The two placements sharded serving needs: ``row_sharded`` splits a
    leading axis one slice per device (model-parallel support slices, or
    data-parallel query rows); ``replicated`` pins a full copy on every
    device. Centralized here so the serving layer never constructs
    partition specs ad hoc — and so the pair is built ONCE per mesh, not
    per dispatch.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    (axis_name,) = mesh.axis_names
    return (NamedSharding(mesh, PartitionSpec(axis_name)),
            NamedSharding(mesh, PartitionSpec()))


def replicate_on_mesh(tree, mesh):
    """``device_put`` every leaf of ``tree`` replicated onto ``mesh``.

    The data-parallel serving layout: the full model on every device,
    query rows partitioned. One explicit placement that callers cache
    beats jit re-broadcasting an uncommitted model on every dispatch —
    the per-call transfer is exactly the overhead the sharded fast path
    exists to remove (docs/PERFORMANCE.md).
    """
    import jax.tree_util

    _, replicated = mesh_shardings(mesh)
    return jax.tree_util.tree_map(
        lambda leaf: jax.device_put(leaf, replicated), tree)


def make_serving_mesh(n_shards: int, axis_name: str = "shard"):
    """1-D mesh over the first ``n_shards`` devices for sharded kPCA serving.

    Unlike ``make_mesh`` this tolerates a machine with MORE devices than
    shards (it takes a prefix) and signals "not enough devices" by returning
    None instead of raising, so callers (``repro.serve.sharded``) can fall
    back to the single-device reduction with identical math. On CPU, set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before the first
    jax call to expose N host devices.
    """
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    if len(devices) < n_shards:
        return None
    return Mesh(np.asarray(devices[:n_shards]), (axis_name,))
