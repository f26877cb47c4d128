"""Production mesh definitions.

Single pod = 16×16 = 256 chips (v5e pod), axes (data, model); multi-pod adds a
leading "pod" axis (2×16×16 = 512 chips).  The pod axis rides the slow DCN/ICI
link, so shardings keep it pure-DP: the only cross-pod collective is the
gradient all-reduce.

Functions (not module constants) so importing never touches jax device state.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(*, model: int | None = None) -> Mesh:
    """Small mesh over whatever devices exist (tests / single-host training)."""
    n = len(jax.devices())
    model = model or 1
    assert n % model == 0, (n, model)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def shrink_mesh(mesh: Mesh, new_dp: int) -> Mesh:
    """Largest sub-mesh with ``new_dp`` data-parallel slots, model axis whole.

    The elastic engine calls this after ``plan_remesh`` shrinks the data
    axis.  When the physical device pool is already at or below the target
    (simulated worlds on a small host mesh), the mesh is returned unchanged —
    the *logical* world still shrinks in the sampler/config.
    """
    model = int(mesh.shape.get("model", 1))
    devs = np.asarray(mesh.devices).reshape(-1, model)
    if new_dp >= devs.shape[0]:
        return mesh
    return Mesh(devs[:new_dp], ("data", "model"))


def mesh_chips(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def dp_size(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in dp_axes(mesh)]))


def tp_size(mesh: Mesh) -> int:
    return int(mesh.shape.get("model", 1))
