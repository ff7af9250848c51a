"""Device mesh construction.

The reference scales by running N independent executor processes, one task
per partition (docs/architecture.md:17-18). The TPU-native equivalent: one
SPMD program over a jax.sharding.Mesh, partitions mapping to mesh shards,
exchanges to XLA collectives over ICI (SURVEY §2.8 mapping table).
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np


def build_mesh(shape: Optional[Dict[str, int]] = None, devices=None):
    """Build a Mesh. shape e.g. {"data": 8}; defaults to all devices on one
    'data' axis (row parallelism — a query engine's natural axis)."""
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    if not shape:
        shape = {"data": len(devices)}
    total = int(np.prod(list(shape.values())))
    if total > len(devices):
        raise ValueError(f"mesh {shape} needs {total} devices, have {len(devices)}")
    devs = np.array(devices[:total]).reshape(tuple(shape.values()))
    return Mesh(devs, tuple(shape.keys()))


def put_sharded(mesh, arr: np.ndarray, axis: str = "data"):
    """Upload a host array split on its leading dimension along `axis`,
    block by block: each device receives only its own rows. `jnp.asarray`
    would land the WHOLE array on device 0 and leave the jitted mesh program
    to redistribute it, so one chip's peak memory would be the entire
    input. (The multi-process path assembles the same layout from per-host
    blocks, multihost.make_sharded.)"""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(arr, NamedSharding(mesh, P(axis)))
