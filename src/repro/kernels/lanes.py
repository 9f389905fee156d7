"""Lane-by-lane batching for Pallas kernels with 1-D or 2-D blocks."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def by_lane(one):
    """``one`` as a function whose vmap runs it lane by lane.

    Under ``jax.vmap`` the lanes go through the kernel one after another
    (``lax.map``): batching the ``pallas_call`` itself would give each
    block a squeezed batch dim, and Mosaic requires the last two block
    dims to tile by (8, 128) or span the array.  Unbatched arguments are
    broadcast to every lane."""
    fn = jax.custom_batching.custom_vmap(one)

    @fn.def_vmap
    def _lanes(axis_size, in_batched, *args):
        args = tuple(x if b else jnp.broadcast_to(x, (axis_size, *x.shape))
                     for x, b in zip(args, in_batched))
        return jax.lax.map(lambda a: one(*a), args), True

    return fn
