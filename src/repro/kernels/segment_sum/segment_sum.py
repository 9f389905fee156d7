"""Blocked one-hot segment-sum — Pallas TPU kernel.

The Dragonfly fast path's link-load accumulation is a scatter-add
(np.bincount with weights): 1-2M (link id, bytes) pairs accumulated
into ~56k link bins, four times per phase.  Scatter is the one shape
TPUs hate, so the kernel recasts it MXU/VPU-friendly as a blocked
one-hot reduction:

  * the pair stream is tiled into [block_pairs] chunks, the segment
    axis into [block_segs] chunks;
  * grid = (segment_blocks, pair_blocks) with the PAIR dim innermost,
    so each output block stays resident in VMEM across the whole pair
    sweep (init at pair-block 0, accumulate, flush once);
  * each step builds the one-hot mask (ids == seg_base + iota) for its
    tile and reduces mask*values over the pair axis.

Out-of-range ids (the padding the wrapper adds to reach a block
multiple) match no segment and vanish.

Block widths: XLA tiles a 1-D f32/int32 array of 1024 or more elements
as T(1024) on a TPU, and Mosaic refuses a 1-D block whose own tiling
differs from the operand's.  Blocks are therefore 1024 wide, or the
whole (shorter) axis; tests/test_tpu_compile.py compiles the engine's
real widths for a described v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _segment_sum_kernel(ids_ref, val_ref, o_ref, *, block_segs: int):
    j = pl.program_id(1)                  # pair-block index (inner dim)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    seg_base = pl.program_id(0) * block_segs
    ids = ids_ref[...]                    # [block_pairs] int32
    vals = val_ref[...].astype(jnp.float32)
    seg = seg_base + jax.lax.broadcasted_iota(
        jnp.int32, (ids.shape[0], block_segs), 1)
    hit = ids[:, None] == seg             # [block_pairs, block_segs]
    o_ref[...] += jnp.sum(jnp.where(hit, vals[:, None], 0.0), axis=0)


@functools.partial(jax.jit, static_argnames=("num_segments", "block_pairs",
                                             "block_segs", "interpret"))
def segment_sum_pallas(values, segment_ids, num_segments: int, *,
                       block_pairs: int = 1024, block_segs: int = 1024,
                       interpret: bool = False):
    """values: [n] float; segment_ids: [n] int -> [num_segments] float32.

    Under ``jax.vmap`` the lanes go through the kernel one after another
    (``lax.map``): batching the ``pallas_call`` itself would give each
    1-D block a squeezed batch dim, and Mosaic requires the last two
    block dims to tile by (8, 128) or span the array."""
    return _lane_mapped(num_segments, block_pairs, block_segs,
                        interpret)(values, segment_ids)


@functools.lru_cache(maxsize=None)
def _lane_mapped(num_segments: int, block_pairs: int, block_segs: int,
                 interpret: bool):
    one = functools.partial(_segment_sum_1d, num_segments=num_segments,
                            block_pairs=block_pairs, block_segs=block_segs,
                            interpret=interpret)
    fn = jax.custom_batching.custom_vmap(one)

    @fn.def_vmap
    def _lanes(axis_size, in_batched, values, segment_ids):
        args = tuple(x if b else jnp.broadcast_to(x, (axis_size, *x.shape))
                     for x, b in zip((values, segment_ids), in_batched))
        return jax.lax.map(lambda a: one(*a), args), True

    return fn


def _segment_sum_1d(values, segment_ids, *, num_segments: int,
                    block_pairs: int, block_segs: int, interpret: bool):
    n = values.shape[0]
    bp = max(1, min(block_pairs, n))
    bs = max(1, min(block_segs, num_segments))
    n_pad = -(-max(n, 1) // bp) * bp
    segs_pad = -(-num_segments // bs) * bs
    ids = jnp.full(n_pad, segs_pad, dtype=jnp.int32)
    ids = ids.at[:n].set(segment_ids.astype(jnp.int32))
    vals = jnp.zeros(n_pad, dtype=jnp.float32)
    vals = vals.at[:n].set(values.astype(jnp.float32))
    out = pl.pallas_call(
        functools.partial(_segment_sum_kernel, block_segs=bs),
        grid=(segs_pad // bs, n_pad // bp),
        in_specs=[
            pl.BlockSpec((bp,), lambda i, j: (j,)),
            pl.BlockSpec((bp,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((bs,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((segs_pad,), jnp.float32),
        interpret=interpret,
    )(ids, vals)
    return out[:num_segments]
