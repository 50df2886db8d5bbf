"""The one door to JAX's sharding surface (written for JAX 0.9).

``jax.make_mesh`` axis types, ``jax.set_mesh`` and ``jax.shard_map`` are
called here and nowhere else (the ``compat-only`` lint rule enforces it),
so a JAX release that moves them changes one file.

* ``make_mesh(shape, axes, devices=...)`` — every axis ``AxisType.Auto``.
* ``set_mesh(mesh)`` — context manager making ``mesh`` the ambient mesh.
* ``shard_map(f, mesh, in_specs, out_specs, axis_names=...)`` — partial-
  manual: ``axis_names`` are the manual axes, the rest stay auto (GSPMD).
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices)


def set_mesh(mesh):
    """Context manager making ``mesh`` the ambient mesh for jit/lowering."""
    return jax.set_mesh(mesh)


def shard_map(f, *, mesh, in_specs, out_specs,
              axis_names: Optional[Sequence[Any]] = None,
              check: bool = False):
    """Partial-manual shard_map: ``axis_names`` are the manual axes, every
    other mesh axis stays auto (GSPMD)."""
    manual = set(axis_names) if axis_names is not None else set(mesh.axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=manual,
                         check_vma=check)
