"""Device mesh construction with factorized axes.

The reference enumerates physical GPUs/CPUs through the Legion machine model
and assigns point tasks to them in the mapper (reference:
src/mapper/mapper.cc:222-322). On TPU the analogous object is a
`jax.sharding.Mesh`. To let SOAP-style per-op configs pick *any*
power-of-two partition degree per tensor dim, we build the mesh with one
axis per prime factor of the device count (e.g. 8 devices → axes
f0,f1,f2 each of size 2). A partition degree d then maps to a tuple of
consecutive axes whose sizes multiply to d (parallel/sharding.py), and two
ops that shard the same logical dim with the same degree land on identical
device assignments — no spurious resharding.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh


def _prime_factors(n: int) -> List[int]:
    fs = []
    d = 2
    while d * d <= n:
        while n % d == 0:
            fs.append(d)
            n //= d
        d += 1
    if n > 1:
        fs.append(n)
    return fs


def structural_axis_sizes(n: int) -> List[int]:
    """THE axis factorization make_mesh builds for n devices (largest
    prime factor first). Search feasibility, offline-target simulation,
    and mesh construction all defer here so a strategy planned for an
    n-device target matches the mesh compile() will build."""
    return sorted(_prime_factors(n), reverse=True) or [1]


def make_mesh(devices: Optional[Sequence] = None,
              num_devices: Optional[int] = None) -> Mesh:
    """Build a factorized mesh over `devices` (default: all jax devices).

    Axis names are "f0", "f1", ... ordered largest factor first so that
    low-index axes (consumed first by degree assignment) correspond to the
    most ICI-local device groups under the default device ordering.
    """
    if devices is None:
        devices = jax.devices()
        if num_devices is not None:
            if num_devices > len(devices):
                raise ValueError(
                    f"requested {num_devices} devices but only "
                    f"{len(devices)} are available (use "
                    f"utils.testing.ensure_cpu_devices to virtualize a "
                    f"larger CPU mesh for testing)")
            devices = devices[:num_devices]
    devices = list(devices)
    n = len(devices)
    factors = structural_axis_sizes(n)
    names = tuple(f"f{i}" for i in range(len(factors)))
    arr = np.array(devices).reshape(tuple(factors))
    return Mesh(arr, names)


def smap(f, mesh: Mesh, in_specs, out_specs):
    """`jax.shard_map` with the replication check off — the one form the
    package uses (Pallas calls and hand-routed collectives inside the
    body carry no replication rule)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def mesh_axis_sizes(mesh: Mesh) -> List[int]:
    return [mesh.shape[name] for name in mesh.axis_names]


def total_devices(mesh: Mesh) -> int:
    n = 1
    for s in mesh_axis_sizes(mesh):
        n *= s
    return n
