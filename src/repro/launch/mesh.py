"""Mesh construction over this process's devices.

Meshes are built by functions, never at import, so importing this module
never touches JAX device state (a caller may still set ``XLA_FLAGS`` before
the first JAX initialization).

Axis semantics (see DESIGN.md §3):
  pod    cross-pod data parallelism (train) / extra cluster parallelism (PIR)
  data   batch shards (train/serve) == PIR "DPU clusters" (DB replicas)
  model  tensor parallelism (heads/ffn/vocab/experts) == PIR DB shards
         (the "DPUs of one cluster")
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np


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


def pir_shard_axis(mesh: jax.sharding.Mesh) -> Optional[str]:
    """Axis that shards the PIR database inside one cluster."""
    return "model" if "model" in mesh.axis_names else None
