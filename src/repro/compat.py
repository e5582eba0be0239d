"""Mesh and Pallas-output helpers shared by the whole tree.

``make_mesh`` builds meshes whose axes are ``AxisType.Auto``.  Since jax 0.7
``jax.make_mesh`` defaults to Explicit axes, under which gathers,
``with_sharding_constraint`` and eager scatters on sharded arrays demand
explicit ``out_sharding``s; the model code is written for GSPMD propagation
(Auto), so every mesh is built here, that way.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AbstractMesh, AxisType

__all__ = [
    "make_mesh",
    "vma_struct",
    "abstract_mesh",
]


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def vma_struct(shape, dtype, *like):
    """ShapeDtypeStruct whose varying-manual-axes set is the union of the
    inputs' — required for pallas_call outputs under shard_map(check_vma)."""
    vma = frozenset().union(*(getattr(jax.typeof(x), "vma", frozenset()) for x in like))
    if not vma:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def abstract_mesh(axis_sizes, axis_names):
    """Device-free mesh handle (Auto axes)."""
    return AbstractMesh(
        tuple(axis_sizes), tuple(axis_names),
        axis_types=(AxisType.Auto,) * len(axis_names),
    )

