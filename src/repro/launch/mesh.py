"""Production mesh builders.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS for 512 host devices
before calling it; smoke tests never call it.

Every mesh uses ``Auto`` axis types: the sharding rules here are hints to
the SPMD partitioner (``with_sharding_constraint``), which ``Explicit``
axes (``jax.make_mesh``'s default) reject.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n: int):
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / elastic re-mesh)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=_auto(len(axes)))


def make_abstract_mesh(shape, axes):
    """Device-free AbstractMesh with the same Auto axis types."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(axes),
                        axis_types=_auto(len(axes)))


def data_axis_size(mesh) -> int:
    size = 1
    for name in ("pod", "data"):
        if name in mesh.axis_names:
            size *= mesh.shape[name]
    return size
