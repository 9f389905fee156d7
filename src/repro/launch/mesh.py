"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS first).  Mesh
construction goes through repro.compat.make_mesh, which gives every
axis Auto sharding (see docs/compat.md)."""

from __future__ import annotations

from repro import compat


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 ("data","model") single pod; 2x16x16 ("pod","data","model")
    for the 512-chip two-pod configuration."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_mesh_for(shape: tuple, axes: tuple):
    """Elastic variant: build whatever mesh the ElasticPlanner chose."""
    return compat.make_mesh(tuple(shape), tuple(axes))
