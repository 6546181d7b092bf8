"""Mesh + shard_map helpers: the one place the repo touches these jax APIs.

Every ``shard_map`` / ``make_mesh`` / ambient-mesh / axis-size call in the
repo routes through this module, so a jax API change lands here and
nowhere else.  Written for the jax pinned in ``requirements.txt``.
"""

from __future__ import annotations

import jax
from jax import lax
from jax.sharding import AxisType, Mesh


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool | None = None):
    """``jax.shard_map``; ``check_vma=None`` keeps jax's default."""
    kwargs = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         **kwargs)


def balanced_dims(ndev: int) -> tuple[int, int]:
    """Factor ``ndev`` into the most-square (a, b) with a*b == ndev, a <= b
    — the 2-D process grid the examples/benchmarks use for pencil plans."""
    a = int(ndev**0.5)
    while ndev % a:
        a -= 1
    return a, ndev // a


def make_mesh(shape: tuple[int, ...], axis_names: tuple[str, ...], *,
              devices=None) -> Mesh:
    """``jax.make_mesh`` with explicit Auto axis types, over ``devices``
    (default: all of ``jax.devices()``)."""
    return jax.make_mesh(shape, axis_names, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def set_mesh(mesh: Mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


def axis_size(axis_name) -> int:
    """Static size of a named mesh axis (or tuple of axes) inside shard_map."""
    return int(lax.axis_size(axis_name))
