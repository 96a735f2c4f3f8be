"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count on first backend init; the dry-run must
set XLA_FLAGS before that happens).

The production topology (per the brief): one pod = 16 x 16 = 256 chips
("data" x "model"); multi-pod = 2 pods = 512 chips with a leading "pod"
axis mapped to the slow (DCN) tier — the ExaNoDe analog of one MCM's
chip-to-chip LVDS mesh vs the 10 Gbps SFP+ links between MCMs.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, names):
    """``jax.make_mesh`` with every axis ``Auto``: the model code shards
    through bare-``PartitionSpec`` constraints, which JAX (>= 0.7) only
    accepts on Auto axes (``make_mesh`` now defaults to Explicit)."""
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(shape))


def mesh_from_spec(spec: str):
    """``"2x4"`` -> a (data, model) mesh; one axis-naming table for every
    driver (launch/train, launch/serve, Runtime.create all resolve spec
    strings here).

    1 dim  -> ("model",);  2 dims -> ("data", "model");
    3 dims -> ("pod", "data", "model") with the leading axis on the slow
    (DCN) tier."""
    try:
        dims = tuple(int(x) for x in spec.split("x"))
    except ValueError:
        raise ValueError(
            f"mesh spec {spec!r}: want 1-3 'x'-separated integer dims "
            "(e.g. '8', '2x4', '2x2x2')") from None
    names = {1: ("model",), 2: ("data", "model"),
             3: ("pod", "data", "model")}
    if len(dims) not in names:
        raise ValueError(f"mesh spec {spec!r}: want 1-3 'x'-separated dims "
                         "(e.g. '8', '2x4', '2x2x2')")
    if any(d <= 0 for d in dims):
        raise ValueError(f"mesh spec {spec!r}: every dim must be positive")
    return make_mesh(dims, names[len(dims)])


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(*, multi_pod: bool = False):
    """8-device mesh for CPU integration tests (2x2x2 or 2x4)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_axes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
