"""Mesh and ``shard_map`` helpers on the installed jax (0.9).

The names below are the repo's single import point for the sharding
API: ``shard_map`` (``check_vma=``), ``make_mesh`` with explicit Auto
axis types, the ambient-mesh context ``set_mesh`` and its reader
``get_abstract_mesh``, plus :func:`default_edge_mesh`.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

shard_map = jax.shard_map
set_mesh = jax.set_mesh
get_abstract_mesh = jax.sharding.get_abstract_mesh


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis typed Auto."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def default_edge_mesh(max_shards: int | None = None,
                      axis_names=("data", "model")):
    """The ("data", "model") edge-sharding mesh over the first
    ``max_shards`` local devices (all of them by default).

    Every edge-parallel entry point in this repo (`core.distributed`,
    `stream.source`, the distributed test lane and benchmarks) shards
    edges over "data"; this helper builds that mesh from however many
    devices the process sees — 1 in plain tier-1 runs, 8 under the CI
    lane's ``XLA_FLAGS=--xla_force_host_platform_device_count=8``, 4 on
    a four-chip TPU host.
    """
    devs = jax.devices()
    n = len(devs) if max_shards is None else min(len(devs), max_shards)
    return Mesh(np.array(devs[:n]).reshape(n, 1), axis_names)
