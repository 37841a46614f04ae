"""Mesh construction for the production pod topologies.

``make_production_mesh`` is a *function* (not a module-level constant) so that
importing this module never touches JAX device state — critical because the
dry-run launcher must set ``XLA_FLAGS=--xla_force_host_platform_device_count``
before the first JAX initialization, while unit tests must see the single real
CPU device.

Axis semantics (see DESIGN.md §3):
  pod    cross-pod data parallelism (train) / extra cluster parallelism (PIR)
  data   batch shards (train/serve) == PIR "DPU clusters" (DB replicas)
  model  tensor parallelism (heads/ffn/vocab/experts) == PIR DB shards
         (the "DPUs of one cluster")
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np

from repro.config import MeshConfig

SINGLE_POD = MeshConfig(shape=(16, 16), axes=("data", "model"))
MULTI_POD = MeshConfig(shape=(2, 16, 16), axes=("pod", "data", "model"))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(cfg: MeshConfig) -> jax.sharding.Mesh:
    """Build a mesh for an arbitrary MeshConfig (used by tests & elastic)."""
    return jax.make_mesh(tuple(cfg.shape), tuple(cfg.axes))


def make_local_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """A ``data x model`` mesh over the first devices of this process.

    Raises when the process has fewer than ``data * model`` devices: a
    request for a sharded layout never quietly becomes a smaller one.
    """
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"a {data}x{model} mesh needs {data * model} "
                         f"devices; this process has {n}")
    devs = np.asarray(jax.devices()[: data * model]).reshape(data, model)
    return jax.sharding.Mesh(devs, ("data", "model"))


def split_devices(n_groups: int, devices=None, *,
                  min_per_group: int = 1) -> list:
    """Partition the live device list into ``n_groups`` disjoint groups.

    The replica plane carves one serve replica per group (each group then
    becomes its own sub-mesh via ``runtime/elastic.carve_submeshes``).
    Groups are equal-sized; leftover devices idle until the next resize
    (same policy as ``plan_mesh``). When the host has fewer than
    ``n_groups * min_per_group`` devices, every group gets the FULL device
    list — the single-host degenerate case: replicas share silicon but
    keep separate schedulers, compiled steps, and DB placements, exactly
    how k parties share the one CPU device on this container.
    """
    if n_groups < 1:
        raise ValueError(f"n_groups must be >= 1, got {n_groups}")
    devs = list(devices if devices is not None else jax.devices())
    per = len(devs) // n_groups
    if per < max(min_per_group, 1):
        return [list(devs) for _ in range(n_groups)]
    return [devs[i * per:(i + 1) * per] for i in range(n_groups)]


def mesh_axis_size(mesh: jax.sharding.Mesh, name: str) -> int:
    return mesh.shape.get(name, 1)


def batch_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """Axes over which the global batch is sharded."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def pir_cluster_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """Axes that enumerate PIR clusters (DB replicas)."""
    return batch_axes(mesh)


def pir_shard_axis(mesh: jax.sharding.Mesh) -> Optional[str]:
    """Axis that shards the PIR database inside one cluster."""
    return "model" if "model" in mesh.axis_names else None
