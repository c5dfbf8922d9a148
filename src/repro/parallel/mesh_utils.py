"""Mesh refactoring: map the fixed production mesh onto logical axes.

The production mesh is ``(data, model)`` / ``(pod, data, model)`` (spec-fixed).
Frameworks need finer logical axes — AF2+BP wants ``model -> branch x dap``;
LMs want ``model -> tp``.  ``refactor_mesh`` rebuilds a Mesh over the *same*
device order with an axis split, so the physical layout (ICI neighborhoods)
is preserved: sub-axes of a contiguous axis stay contiguous.
"""
from __future__ import annotations

import math
from typing import Mapping, Sequence

import jax
from jax.sharding import AxisType, Mesh


def refactor_mesh(mesh: Mesh, split: Mapping[str, Sequence[tuple[str, int]]]) -> Mesh:
    """Split named axes: ``refactor_mesh(m, {"model": [("branch",2),("dap",8)]})``.

    Axes not mentioned keep their name/extent. Sub-axis sizes must multiply to
    the split axis's extent; earlier sub-axes are outer (coarser) in device
    order.
    """
    old_names = list(mesh.axis_names)
    new_shape: list[int] = []
    new_names: list[str] = []
    for name in old_names:
        extent = mesh.shape[name]
        if name in split:
            subs = list(split[name])
            prod = math.prod(s for _, s in subs)
            if prod != extent:
                raise ValueError(
                    f"split of axis {name!r} (extent {extent}) into {subs} "
                    f"multiplies to {prod}")
            for sub_name, sub_size in subs:
                new_names.append(sub_name)
                new_shape.append(sub_size)
        else:
            new_names.append(name)
            new_shape.append(extent)
    devices = mesh.devices.reshape(new_shape)
    return Mesh(devices, tuple(new_names))


def rename_mesh(mesh: Mesh, renames: Mapping[str, str]) -> Mesh:
    names = tuple(renames.get(n, n) for n in mesh.axis_names)
    return Mesh(mesh.devices, names)


def axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def make_mesh(shape: Sequence[int], names: Sequence[str], *,
              devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``.

    The repo's steps mix ``shard_map`` (AF2) with GSPMD
    ``with_sharding_constraint`` on ``P('data', ...)`` (LM zoo); both need
    Auto axes, and ``jax.make_mesh`` defaults to Explicit ones."""
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def smap(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off (BP's
    axis_index-dependent branches are deliberately non-replicated
    mid-computation)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def local_slice(x, axis_name: str, dim: int):
    """Inside shard_map: take this device's equal slice of ``x`` along ``dim``."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    size = x.shape[dim] // n
    return jax.lax.dynamic_slice_in_dim(x, idx * size, size, dim)
