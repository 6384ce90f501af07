"""Production mesh construction.

Functions, not module-level constants, so importing this module never touches
jax device state (the dry-run driver sets XLA_FLAGS before any jax import).

Mesh layout (TPU v5e-class pods of 256 chips):

  single-pod : (16, 16)    axes ("data", "model")
  multi-pod  : (2, 16, 16) axes ("pod", "data", "model")

FL semantics on top of the mesh: the *worker* axes (pod and/or data) index
Pollen's FL workers; the model axis carries TP/EP; FSDP uses (pod, data).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_test_mesh", "axis_sizes",
           "fl_shard_devices", "fl_combine_topology"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh over however many (host) devices exist — smoke tests."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def fl_shard_devices(n_shards: int, *, mesh=None, fl_axes=("pod", "data")):
    """Lead devices of the mesh's FL-worker shards, cycled to ``n_shards``.

    The engine's mesh execution path dispatches one program per FL worker
    and places it on its shard's device group; this returns one
    representative device per shard — with a mesh, the first device of each
    slice along the FL-worker axes (the ``model`` axis carries TP *within*
    a shard, so every shard's group is a contiguous block along it);
    without one, ``jax.devices()`` round-robin.  On a single-device host
    every shard resolves to that device — the decomposition then still buys
    per-worker syncs and per-shard cache pools, just not parallel devices.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if mesh is None:
        devs = list(jax.devices())
    else:
        devs = _fl_lead_devices(mesh, fl_axes)
    return [devs[s % len(devs)] for s in range(n_shards)]


def fl_combine_topology(n_shards: int, *, mesh=None,
                        fl_axes=("pod", "data")) -> tuple:
    """Device binding of the hierarchical combine tree
    (``EngineConfig.combine_mode="tree"``): ``(shard_devices, root)``.

    ``shard_devices[s]`` hosts shard ``s``'s partial-merge program (the
    shard's lead device — the merge consumes partials already resident
    there, so no bytes cross shards before it), and ``root`` hosts the
    cross-shard combine: one O(params)-sized partial per shard crosses to
    it, instead of every lane partial.  The root is the first shard's lead
    device — on a real mesh, the server-side reduce of §3.3.  On a
    single-device host all entries are that device and the topology only
    structures the programs.
    """
    devs = fl_shard_devices(n_shards, mesh=mesh, fl_axes=fl_axes)
    return devs, devs[0]


def _fl_lead_devices(mesh, fl_axes):
    names = list(mesh.axis_names)
    keep = [i for i, a in enumerate(names) if a in fl_axes]
    grid = mesh.devices
    if keep:
        # Collapse non-FL axes to their first coordinate: one lead
        # device per FL-axis slice, in FL-axis-major order.
        idx = tuple(slice(None) if i in keep else 0
                    for i in range(grid.ndim))
        return list(grid[idx].reshape(-1))
    return [grid.reshape(-1)[0]]
