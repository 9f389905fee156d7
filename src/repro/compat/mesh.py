"""Mesh helpers over the installed jax.

``jax.make_mesh`` gives every axis ``AxisType.Explicit`` by default;
the sharding rules of the language-model stack are written for
auto-sharded axes, so meshes are built through :func:`make_mesh`.
"""

from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """`jax.make_mesh` with every axis ``AxisType.Auto``."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names),
                         devices=devices)


def abstract_axis_sizes() -> dict:
    """{axis_name: size} of the active mesh ({} outside ``jax.set_mesh``)."""
    mesh = jax.sharding.get_abstract_mesh()
    return {a: mesh.shape[a] for a in mesh.axis_names}
