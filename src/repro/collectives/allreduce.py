"""All-reduce schedules (shard_map) — DIRECT vs HIERARCHICAL.

DIRECT:        psum over every participating axis in one phase.  On a
               multi-pod mesh the ring spans pods, so the slow DCN links
               carry the full 2(n-1)/n ring share.

HIERARCHICAL:  psum_scatter over the intra-pod axis (fast ICI), psum over
               the pod axis on the 1/inner shard (slow links carry
               bytes/inner_size), all_gather back over the intra-pod axis.
               One extra phase ("hop") in exchange for offloading the
               scarce links — exactly the minimal/non-minimal trade the
               paper arbitrates per message.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _flatten_pad(x, n):
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, pad


def allreduce_direct(x, axes):
    """Inside shard_map: one-phase psum over (possibly multiple) axes."""
    return jax.lax.psum(x, axes)


def allreduce_hierarchical(x, pod_axis: str, inner_axis: str,
                           inner_size: int):
    """Inside shard_map: RS(inner) -> AR(pod) -> AG(inner).

    Works for any tensor shape (flattens + pads to inner_size)."""
    orig_shape = x.shape
    flat, pad = _flatten_pad(x, inner_size)
    shard = jax.lax.psum_scatter(
        flat.reshape(inner_size, -1), inner_axis, scatter_dimension=0,
        tiled=False)
    shard = jax.lax.psum(shard, pod_axis)
    full = jax.lax.all_gather(shard, inner_axis, axis=0, tiled=False)
    flat = full.reshape(-1)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(orig_shape)


def grad_allreduce(grads, mesh, *, mode, pod_axis: str = "pod",
                   inner_axis: str = "data"):
    """Mean-reduce a gradient pytree across the data-parallel axes with the
    chosen schedule.  Entry point used by train/grad_comm.py.

    grads leaves are data-parallel replicas (one per (pod, data) position);
    the tree is returned averaged."""
    from repro.collectives.modes import CollectiveMode

    axis_names = mesh.axis_names
    has_pod = pod_axis in axis_names
    dp_axes = ((pod_axis, inner_axis) if has_pod else (inner_axis,))
    n_dp = 1
    for a in dp_axes:
        n_dp *= mesh.shape[a]
    inner_size = mesh.shape[inner_axis]

    def reduce_leaf(g):
        if mode == CollectiveMode.HIERARCHICAL and has_pod:
            g = allreduce_hierarchical(g, pod_axis, inner_axis, inner_size)
        else:
            g = allreduce_direct(g, dp_axes)
        return g / n_dp

    def spec_for(leaf):
        return P()  # per-device partial sums along the dp axes

    in_specs = jax.tree_util.tree_map(spec_for, grads)
    return jax.shard_map(
        lambda g: jax.tree_util.tree_map(reduce_leaf, g),
        mesh=mesh, in_specs=(in_specs,), out_specs=in_specs,
        check_vma=False,
    )(grads)
