"""Device mesh management.

The reference's parallelism substrate is KVStore comm trees + ps-lite
(SURVEY.md §2.5/§5.8); the TPU-native substrate is a ``jax.sharding.Mesh``
with named axes and XLA collectives over ICI/DCN.  Axis convention:

- ``dp`` — data parallel (batch sharding; grads all-reduced by XLA)
- ``tp`` — tensor parallel (weight sharding inside layers)
- ``pp`` — pipeline parallel (stage sharding, see .pipeline — forward
  AND the 1F1B/GPipe backward training schedule with microbatch grad
  accumulation, reachable via ``make_train_step(pipeline_stages=...)``)
- ``sp`` — sequence/context parallel (ring attention, see .ring_attention)
- ``ep`` — expert parallel (MoE expert sharding, see .moe — aux
  load-balancing loss + capacity factor route through the fused step)
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["Mesh", "NamedSharding", "PartitionSpec", "P", "make_mesh",
           "replicated", "shard_along", "current_devices", "shard_map",
           "global_devices", "spans_processes"]

P = PartitionSpec


def current_devices(platform=None):
    devs = jax.devices()
    if platform:
        devs = [d for d in devs if d.platform == platform]
    return devs


def global_devices(platform=None):
    """Every process's devices in deterministic ``(process_index, id)``
    order — the canonical device list for a process-spanning mesh
    (every process must enumerate identically for one GSPMD program to
    span them; ``parallel/distributed.py::make_process_mesh`` builds on
    this)."""
    return sorted(current_devices(platform),
                  key=lambda d: (d.process_index, d.id))


def spans_processes(mesh: Mesh) -> bool:
    """True when the mesh contains devices of more than one process —
    the multihost/multi-process regime where state arrays are global
    and checkpoints need the per-process commit protocol."""
    return any(d.process_index != jax.process_index()
               for d in mesh.devices.flat)


def make_mesh(axes: Dict[str, int], devices: Optional[Sequence] = None) -> Mesh:
    """Create a Mesh with named axes, e.g. make_mesh({'dp': 4, 'tp': 2}).

    Axis sizes must multiply to the device count; an axis size of -1 is
    inferred from the remaining devices.
    """
    devices = list(devices if devices is not None else jax.devices())
    names = list(axes.keys())
    sizes = list(axes.values())
    unknown = [i for i, s in enumerate(sizes) if s == -1]
    known = int(np.prod([s for s in sizes if s != -1])) if sizes else 1
    if unknown:
        if len(unknown) > 1:
            raise ValueError("only one axis may be -1")
        sizes[unknown[0]] = len(devices) // known
    total = int(np.prod(sizes))
    if total != len(devices):
        raise ValueError("mesh axes %s=%s need %d devices, have %d"
                         % (names, sizes, total, len(devices)))
    arr = np.array(devices).reshape(sizes)
    return Mesh(arr, axis_names=tuple(names))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_along(mesh: Mesh, axis_name: str, dim: int = 0,
                ndim: int = 1) -> NamedSharding:
    spec = [None] * ndim
    spec[dim] = axis_name
    return NamedSharding(mesh, P(*spec))
