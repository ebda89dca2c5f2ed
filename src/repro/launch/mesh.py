"""Production mesh definition (spec-mandated shape) and the per-architecture
derived view that factors the `model` axis into `stage x tensor`.

`make_production_mesh` is a FUNCTION (not a module constant) so importing this
module never touches jax device state.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def derive_pipeline_mesh(prod_mesh: Mesh, pp: int, tp: int) -> Mesh:
    """Factor the production mesh's `model` axis into (`stage`, `tensor`).

    The same physical devices in the same order — only the logical axis names
    change, so the dry-run still exercises exactly the spec'd production mesh
    (DESIGN.md §3).  Works for both (data, model) and (pod, data, model).
    """
    devices = prod_mesh.devices
    if devices.shape[-1] != pp * tp:
        raise ValueError(f"model axis {devices.shape[-1]} != pp*tp = {pp}*{tp}")
    new_shape = devices.shape[:-1] + (pp, tp)
    names = prod_mesh.axis_names[:-1] + ("stage", "tensor")
    return Mesh(
        devices.reshape(new_shape), names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(names))


def manual_axes(mesh: Mesh) -> frozenset:
    """The mesh axes handled manually inside shard_map: everything except a
    `tensor` axis of size > 1, which GSPMD auto-shards from argument
    shardings.  A size-1 `tensor` axis partitions nothing and stays manual,
    so the region is fully manual and Mosaic kernels (which cannot be
    auto-partitioned) can run inside it."""
    return frozenset(n for n in mesh.axis_names
                     if n != "tensor" or mesh.shape[n] == 1)
