"""Production mesh construction.

A function, not a module-level constant — importing this module never
touches jax device state. Shapes: one v5e pod = 16x16 = 256 chips
(data, model); multi-pod = 2 pods = 512 chips with a leading 'pod' axis
that extends data parallelism across the inter-pod links.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # MeshPar's sharding rules are written for Auto axes (GSPMD
    # propagation); jax.make_mesh defaults to Explicit
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Optional[Sequence[str]] = None):
    """Arbitrary mesh for tests/debug (e.g. (2,2,2) on 8 host devices)."""
    shape = tuple(shape)
    if axes is None:
        axes = {2: ("data", "model"),
                3: ("pod", "data", "model")}[len(shape)]
    return _auto_mesh(shape, tuple(axes))


def dp_axes(mesh) -> Tuple[str, ...]:
    """The data-parallel axes: ('pod','data') on multi-pod, ('data',)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_size(mesh, *names) -> int:
    s = 1
    for n in names:
        if n in mesh.axis_names:
            s *= mesh.shape[n]
    return s
