"""Device meshes for user-axis sharded serving.

The sharded engine partitions the *user* population over a 1-D mesh whose
single axis is named ``'users'`` — deliberately distinct from the
training meshes' ``('pod', 'data', 'model')`` axes so the two kinds of
mesh can never be confused (``repro.distributed.meshctx.user_axes``
resolves logical ``'users'`` constraints against either).

On a development box the mesh is synthetic: launch with

    XLA_FLAGS=--xla_force_host_platform_device_count=4

and :func:`user_mesh` sees four ``CpuDevice``s.  Without the flag
:func:`shard_devices` cycles the available CPU devices — the partition,
the per-shard compaction, and the bit-identical reduction are all
preserved; only the physical parallelism collapses onto the shared
device.  Accelerators are never cycled: asking for more shards than
chips raises.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh

__all__ = ["user_mesh", "mesh_shards", "shard_devices"]


def user_mesh(n_shards: int | None = None, devices=None) -> Mesh:
    """A 1-D ``('users',)`` mesh over ``n_shards`` devices.

    ``n_shards=None`` uses every visible device.  Raises if fewer devices
    exist than shards requested — a jax ``Mesh`` cannot repeat a device;
    pass ``shards=`` to :class:`repro.shard.ShardedEngine` instead when
    oversubscribing a small host is the intent.
    """
    devs = list(devices if devices is not None else jax.devices())
    n = len(devs) if n_shards is None else int(n_shards)
    if n < 1:
        raise ValueError(f"need at least one shard, got {n}")
    if n > len(devs):
        raise ValueError(
            f"user_mesh: {n} shards requested but only {len(devs)} device(s) "
            "visible — launch with XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={n} for a synthetic CPU mesh, or pass shards="
            "to ShardedEngine to oversubscribe"
        )
    return Mesh(np.array(devs[:n]), axis_names=("users",))


def mesh_shards(mesh: Mesh) -> int:
    """Shard count of a serving mesh (the size of its ``'users'`` axis)."""
    if "users" not in mesh.axis_names:
        raise ValueError(
            f"expected a ('users',) serving mesh, got axes {mesh.axis_names}"
        )
    return int(mesh.shape["users"])


def shard_devices(n_shards: int, mesh: Mesh | None = None) -> list:
    """One device per shard.  From a mesh: its ``'users'`` axis devices.
    Without one: the visible devices.  CPU devices are cycled when there
    are fewer than shards (a development box still runs every shard
    count); accelerators raise instead, so a shard never shares a chip
    without saying so."""
    if mesh is not None:
        devs = list(mesh.devices.reshape(-1))
        if len(devs) != n_shards:
            raise ValueError(
                f"mesh has {len(devs)} devices but {n_shards} shards requested"
            )
        return devs
    devs = jax.devices()
    n = int(n_shards)
    if n > len(devs) and devs[0].platform != "cpu":
        raise ValueError(
            f"{n} shards requested but only {len(devs)} {devs[0].platform} "
            "device(s) visible"
        )
    return [devs[i % len(devs)] for i in range(n)]
