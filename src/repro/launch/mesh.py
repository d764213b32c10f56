"""Production mesh construction.

One satellite-pod = a 16 x 16 ICI mesh (256 chips: "data" x "model");
multi-pod adds the leading "pod" axis whose hop is the FSO inter-satellite
link (bandwidth from repro.core.isl, not ICI).

Defined as FUNCTIONS, never module-level constants: importing this module
must not touch jax device state (the dry-run pins the device count via
XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # jax.make_mesh defaults to Explicit axes, under which sharding-in-types
    # rejects the models' unannotated gathers; every axis here is Auto, so
    # XLA's partitioner propagates shardings from the jit boundaries
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, shape=None):
    """shape: optional logical (data, model) [or (pod, data, model)]
    override — same 256/512 chips, different axis split (a §Perf knob)."""
    if shape is None:
        shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    assert len(shape) == len(axes)
    return _auto_mesh(shape, axes)


def make_test_mesh(n_devices: int | None = None):
    """Degenerate mesh over whatever devices exist (CPU tests: 1 device)."""
    n = n_devices or len(jax.devices())
    return _auto_mesh((1, n, 1), ("pod", "data", "model"))


def mesh_for(kind: str):
    """CLI-facing dispatcher: --mesh {none,test,single,multi}.

    "test" fits whatever devices exist (the CPU container); "single"/"multi"
    are the 256/512-chip production meshes (dry-run scale — they require the
    matching device count, e.g. via XLA_FLAGS host-device emulation)."""
    if kind == "none":
        return None
    if kind == "test":
        return make_test_mesh()
    if kind == "single":
        return make_production_mesh(multi_pod=False)
    if kind == "multi":
        return make_production_mesh(multi_pod=True)
    raise ValueError(f"unknown mesh kind {kind!r}")
