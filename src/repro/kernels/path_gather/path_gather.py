"""Gather of a per-link vector through path link ids — a Pallas TPU kernel.

The jax phase engine reads per-link vectors (queue estimates, feedback
penalties, utilisation, queues) at every hop of every candidate path:
``table[safe]`` with ``safe`` the [rows, candidates, hops] link ids, a
few million slots a phase from a table of ~56-66k links.  XLA's gather
takes ~6.6 ns a slot on a TPU v5e whatever the table's size.  The table
is a few hundred KB, so this kernel keeps it whole in VMEM (its block's
index map is constant across the grid) and gathers with lane gathers:

  * the table is viewed as [rows, 128] (padded to 8 rows) and the ids
    as [m, 128]; a grid step takes `BLOCK_ROWS` rows of ids, the last
    one what is left;
  * `SUB_ROWS` id rows at a time sit in vregs with their split
    ``hi = id >> 7`` (the table row) and ``lo = id & 127`` (the lane);
  * a loop over the table's rows broadcasts each row across the
    sublanes, gathers its lanes by ``lo`` (``take_along_axis`` on the
    lane axis, one ``tpu.dynamic_gather`` a vreg) and keeps the result
    where ``hi`` is that row.

The result is exactly ``table[ids]`` for ids in ``[0, len(table))``; an
id outside matches no row and reads 0.  The work grows with the ids
times the table's rows, so the kernel pays only below some table size
(the engine's threshold, ``jax_backend.PATH_GATHER_MAX_LINKS``).

Under ``jax.vmap`` the lanes go through the kernel one after another
(``repro.kernels.lanes.by_lane``), each with its own table and ids.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lanes import by_lane

#: lanes of a vreg: the width of the table's and the ids' 2-D views
LANES = 128
#: id rows a grid step takes
BLOCK_ROWS = 512
#: id rows held in vregs while the table's rows go by: on a TPU v5e a
#: gather of 5,760,768 ids from 56,448 links took 31.6 / 21.8 / 17.7 /
#: 14.2 ms at 8 / 16 / 32 / 64 rows (XLA's gather: 42.3 ms)
SUB_ROWS = 64
#: table rows a loop iteration handles: a divisor of 8, so that the
#: last iteration reads no further than the table view's zero rows
UNROLL = 8


def _round_up(n: int, m: int) -> int:
    return -(-max(int(n), 1) // m) * m


def index_rows(n: int) -> int:
    """Rows of a [rows, 128] view of ``n`` ids that `path_gather_pallas`
    takes with no pad of its own: whole (8, 128) tiles."""
    return _round_up(_round_up(n, LANES) // LANES, 8)


def _gather_kernel(tab_ref, idx_ref, o_ref, *, rows: int):
    def sub_block(b, carry):
        s = pl.ds(pl.multiple_of(b * SUB_ROWS, SUB_ROWS), SUB_ROWS)
        idx = idx_ref[s, :]
        hi = jax.lax.shift_right_logical(idx, 7)
        lo = jnp.bitwise_and(idx, LANES - 1)

        def rows_of(j, acc):
            for k in range(UNROLL):       # Mosaic loops unroll by hand
                r = j * UNROLL + k
                t = jnp.broadcast_to(tab_ref[pl.ds(r, 1), :], lo.shape)
                acc = jnp.where(hi == r,
                                jnp.take_along_axis(t, lo, axis=1), acc)
            return acc

        o_ref[s, :] = jax.lax.fori_loop(
            0, -(-rows // UNROLL), rows_of,
            jnp.zeros(lo.shape, jnp.float32))
        return carry

    jax.lax.fori_loop(0, idx_ref.shape[0] // SUB_ROWS, sub_block, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def path_gather_pallas(table, ids, *, interpret: bool = False):
    """table: [n] float32; ids: [m, 128] int32 in ``[0, n)`` ->
    [m, 128] float32, ``table[ids]``.

    Ids of `index_rows` rows go through as they are; a row count that
    is no multiple of 8 is padded here (a copy)."""
    return by_lane(functools.partial(_gather_2d, interpret=interpret))(
        table, ids)


def _gather_2d(table, ids, *, interpret: bool):
    n, m = table.shape[0], ids.shape[0]
    rows = _round_up(n, LANES) // LANES
    tab = jnp.pad(table.astype(jnp.float32),
                  (0, _round_up(rows, 8) * LANES - n)).reshape(-1, LANES)
    m_pad = index_rows(m * LANES)
    if m_pad != m:
        ids = jnp.pad(ids, ((0, m_pad - m), (0, 0)))
    # a last block past the ids reads rows it does not write back
    bm = min(BLOCK_ROWS, _round_up(m_pad, SUB_ROWS))
    out = pl.pallas_call(
        functools.partial(_gather_kernel, rows=rows),
        grid=(pl.cdiv(m_pad, bm),),
        in_specs=[pl.BlockSpec(tab.shape, lambda i: (0, 0)),
                  pl.BlockSpec((bm, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m_pad, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(tab, ids.astype(jnp.int32))
    return out[:m]
