"""Production mesh definition.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state.  Single pod = 16x16 (256 v5e chips); multi-pod
adds a leading "pod" axis (2 pods = 512 chips).  The "pod" axis carries
only data parallelism (and expert parallelism for MoE) — it maps onto DCN,
so nothing bandwidth-hungry (TP) is ever placed on it.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def mesh_name(mesh) -> str:
    return "x".join(str(mesh.shape[a]) for a in mesh.axis_names)
