"""Blocked one-hot segment-sum — Pallas TPU kernels.

The Dragonfly fast path's link-load accumulation is a scatter-add
(np.bincount with weights): 1-4M (link id, bytes) pairs accumulated
into ~56k link bins, five times per phase.  Scatter is the one shape
TPUs hate, so both kernels recast it VPU-friendly as a blocked one-hot
reduction: a step takes one [block] tile of pairs and one [block] tile
of segments, builds the one-hot mask (ids == seg_base + iota) and
reduces mask*values over the pair axis into the segment tile, which
stays resident in VMEM while consecutive steps add to it.

  * ``segment_sum_pallas`` (dense, any id order): grid =
    (segment_blocks, pair_blocks) with the PAIR dim innermost, so every
    segment block sweeps every pair block (init at pair-block 0,
    accumulate, flush once).  Its work grows with pairs x segments.
  * ``segment_sum_sorted_pallas`` (ids ascending): a segment block
    visits only the pair blocks that hold its pairs.  The visit list
    (``sorted_schedule``, built on the host from the segment offsets)
    arrives by scalar prefetch, as in the grouped-matmul ("megablox")
    kernels: grid = (visits,), the pair blocks' index map reads the
    visit's pair block and the output's its segment block.  Its work
    grows with pairs + segments.

Out-of-range ids (the padding the wrappers add to reach a block
multiple) match no segment and vanish.

Block widths: XLA tiles a 1-D f32/int32 array of 1024 or more elements
as T(1024) on a TPU, and Mosaic refuses a 1-D block whose own tiling
differs from the operand's.  Blocks are therefore 1024 wide, or the
whole (shorter) axis of the dense kernel; tests/test_tpu_compile.py
compiles the engine's real widths for a described v5e.

Under ``jax.vmap`` the lanes go through a kernel one after another
(``repro.kernels.lanes.by_lane``).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lanes import by_lane

#: block width of the sorted kernel and of its schedule
BLOCK = 1024

#: kinds of a sorted-kernel visit: add the pair block to the resident
#: segment block; zero the segment block first (its first visit); or
#: nothing (the padding that fixes the schedule's length)
_ADD, _FIRST, _SKIP = 0, 1, 2


def _blocks(n: int, block: int) -> int:
    return -(-max(int(n), 1) // block)


def _accumulate(ids_ref, val_ref, o_ref, seg_base):
    ids = ids_ref[...]                    # [block_pairs] int32
    vals = val_ref[...].astype(jnp.float32)
    seg = seg_base + jax.lax.broadcasted_iota(
        jnp.int32, (ids.shape[0], o_ref.shape[0]), 1)
    hit = ids[:, None] == seg             # [block_pairs, block_segs]
    o_ref[...] += jnp.sum(jnp.where(hit, vals[:, None], 0.0), axis=0)


# ------------------------------------------------------------------ dense
def _dense_blocks(n: int, num_segments: int, block_pairs: int,
                  block_segs: int) -> tuple:
    """(pair block, segment block, padded pairs, padded segments)."""
    bp = max(1, min(block_pairs, n))
    bs = max(1, min(block_segs, num_segments))
    return bp, bs, _blocks(n, bp) * bp, _blocks(num_segments, bs) * bs


def dense_grid_steps(n_pairs: int, num_segments: int) -> int:
    """Grid steps of one ``segment_sum_pallas`` call at its default
    blocks."""
    bp, bs, n_pad, segs_pad = _dense_blocks(n_pairs, num_segments, BLOCK,
                                            BLOCK)
    return (n_pad // bp) * (segs_pad // bs)


def _segment_sum_kernel(ids_ref, val_ref, o_ref):
    @pl.when(pl.program_id(1) == 0)       # pair-block index (inner dim)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    _accumulate(ids_ref, val_ref, o_ref, pl.program_id(0) * o_ref.shape[0])


@functools.partial(jax.jit, static_argnames=("num_segments", "block_pairs",
                                             "block_segs", "interpret"))
def segment_sum_pallas(values, segment_ids, num_segments: int, *,
                       block_pairs: int = 1024, block_segs: int = 1024,
                       interpret: bool = False):
    """values: [n] float; segment_ids: [n] int, any order ->
    [num_segments] float32."""
    return by_lane(functools.partial(
        _segment_sum_1d, num_segments=num_segments, block_pairs=block_pairs,
        block_segs=block_segs, interpret=interpret))(values, segment_ids)


def _segment_sum_1d(values, segment_ids, *, num_segments: int,
                    block_pairs: int, block_segs: int, interpret: bool):
    n = values.shape[0]
    bp, bs, n_pad, segs_pad = _dense_blocks(n, num_segments, block_pairs,
                                            block_segs)
    ids = jnp.full(n_pad, segs_pad, dtype=jnp.int32)
    ids = ids.at[:n].set(segment_ids.astype(jnp.int32))
    vals = jnp.zeros(n_pad, dtype=jnp.float32)
    vals = vals.at[:n].set(values.astype(jnp.float32))
    out = pl.pallas_call(
        _segment_sum_kernel,
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


# ----------------------------------------------------------------- sorted
def sorted_grid_steps(n_pairs: int, num_segments: int) -> int:
    """Visits of a sorted schedule over ``n_pairs`` pairs into
    ``num_segments`` bins: each pair block once, plus one more for each
    segment block (a pair block straddling two segment blocks is
    visited by both; a segment block with no pairs visits one block)."""
    return _blocks(n_pairs, BLOCK) + _blocks(num_segments, BLOCK)


def sorted_schedule(seg_off) -> np.ndarray:
    """Host-side visit list of ``segment_sum_sorted_pallas``.

    ``seg_off``: [num_segments + 1] offsets of each segment's pairs in
    the sorted pair list (segment ``s`` holds pairs
    ``seg_off[s]:seg_off[s+1]``).  Returns int32 [3 * visits]: the
    visits' segment blocks, then their pair blocks, then their kinds,
    one flat array so the kernel prefetches it whole.  Segment blocks
    ascend and each one's pair blocks ascend; there are
    ``sorted_grid_steps`` visits, the unused ones at the end repeating
    the last as no-ops, so the length, and the kernel's grid, depend
    only on the sizes."""
    off = np.asarray(seg_off, dtype=np.int64)
    n_segs = off.shape[0] - 1
    n_pb = -(-int(off[-1]) // BLOCK)
    sb = np.arange(_blocks(n_segs, BLOCK))
    lo = off[sb * BLOCK]
    hi = off[np.minimum((sb + 1) * BLOCK, n_segs)]
    first = np.minimum(lo // BLOCK, max(n_pb - 1, 0))
    last = np.maximum(first, (hi - 1) // BLOCK)   # an empty block: one
    count = last - first + 1
    seg = np.repeat(sb, count)
    start = np.cumsum(count) - count
    step = np.arange(seg.shape[0]) - start[seg]
    kind = np.where(step == 0, _FIRST, _ADD)
    rows = np.stack([seg, first[seg] + step, kind])
    skip = sorted_grid_steps(off[-1], n_segs) - seg.shape[0]
    pad = np.repeat(rows[:, -1:], skip, axis=1)
    pad[2] = _SKIP
    return np.concatenate([rows, pad], axis=1).astype(np.int32).ravel()


def _sorted_kernel(sched_ref, ids_ref, val_ref, o_ref, *, visits: int):
    v = pl.program_id(0)
    kind = sched_ref[2 * visits + v]

    @pl.when(kind == _FIRST)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(kind != _SKIP)
    def _add():
        _accumulate(ids_ref, val_ref, o_ref, sched_ref[v] * BLOCK)


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def segment_sum_sorted_pallas(values, segment_ids, schedule,
                              num_segments: int, *, interpret: bool = False):
    """values: [n] float; segment_ids: [n] int, ascending over the pairs
    the schedule covers; schedule: `sorted_schedule` of their offsets ->
    [num_segments] float32.  Pairs past the schedule's last pair block
    are not read."""
    return by_lane(functools.partial(
        _sorted_1d, num_segments=num_segments, interpret=interpret))(
            values, segment_ids, schedule)


def _sorted_1d(values, segment_ids, schedule, *, num_segments: int,
               interpret: bool):
    n = values.shape[0]
    n_pad = _blocks(n, BLOCK) * BLOCK
    segs_pad = _blocks(num_segments, BLOCK) * BLOCK
    ids = segment_ids.astype(jnp.int32)
    vals = values.astype(jnp.float32)
    if n_pad != n:
        ids = jnp.pad(ids, (0, n_pad - n), constant_values=segs_pad)
        vals = jnp.pad(vals, (0, n_pad - n))
    visits = schedule.shape[0] // 3

    def pair_block(v, sched):
        return (sched[visits + v],)

    out = pl.pallas_call(
        functools.partial(_sorted_kernel, visits=visits),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(visits,),
            in_specs=[pl.BlockSpec((BLOCK,), pair_block),
                      pl.BlockSpec((BLOCK,), pair_block)],
            out_specs=pl.BlockSpec((BLOCK,), lambda v, sched: (sched[v],)),
        ),
        out_shape=jax.ShapeDtypeStruct((segs_pad,), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(schedule, ids, vals)
    return out[:num_segments]
