"""Activation sharding hints, resolved against the ambient abstract mesh.

XLA SPMD propagation loses batch/model sharding through scan-of-remat-block
bodies, so models annotate their activations with *logical* axes:

    x = shard_hint(x, ("batch", None, "model"))

"batch" resolves to whichever of ("pod", "data") the current mesh has; any
axis that does not divide the corresponding dimension is dropped (e.g. a
4-head arch on a 16-way model axis, or batch=1 long-context decode). With no
mesh set (unit tests, single-CPU runs) this is a no-op — models never need a
concrete mesh object.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import PartitionSpec as P

BATCH_AXES = ("pod", "data")


def shard_hint(x, spec):
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    names = set(mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    resolved = []
    for dim, ax in zip(x.shape, spec):
        if ax == "batch":
            cand = tuple(a for a in BATCH_AXES if a in names)
            cand = cand if cand else None
        elif ax == "fsdp":
            cand = ("data",) if "data" in names else None
        elif isinstance(ax, str):
            cand = (ax,) if ax in names else None
        elif isinstance(ax, tuple):
            cand = tuple(a for a in ax if a in names) or None
        else:
            cand = None
        if cand is not None:
            n = math.prod(sizes[a] for a in cand)
            if n == 0 or dim % n != 0:
                cand = None
        resolved.append(cand if cand is None or len(cand) > 1
                        else cand[0])
    return jax.lax.with_sharding_constraint(x, P(*resolved))


def mesh_axis_size(name: str):
    """Size of a mesh axis in the ambient abstract mesh, or None."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return None
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    return sizes.get(name)
