"""Production mesh definitions (TPU v5e target).

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run sets the 512-device XLA flag before import).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi-pod adds a leading pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def _auto(n: int):
    """Worker meshes are driven by shard_map with explicit in/out specs;
    ``Auto`` axes keep the arrays' shardings out of their types, so a
    replicated ``P()`` and the ``P(None, ...)`` a step returns compile
    to one executable."""
    return (jax.sharding.AxisType.Auto,) * n


def make_worker_mesh(nworkers: int, axis: str = "workers"):
    """1-D graph-parallel mesh for the distributed GCN trainer."""
    return jax.make_mesh((nworkers,), (axis,), axis_types=_auto(1))


def make_hier_worker_mesh(num_groups: int, group_size: int,
                          group_axis: str = "group", node_axis: str = "node"):
    """2-D mesh for the two-level halo exchange: (groups, workers-per-group).

    The inner (node) axis should map to devices sharing the fast fabric
    (sockets of one node); jax.make_mesh's default device assignment keeps
    the trailing axis innermost, which matches typical process layouts.
    """
    return jax.make_mesh((num_groups, group_size), (group_axis, node_axis),
                         axis_types=_auto(2))
