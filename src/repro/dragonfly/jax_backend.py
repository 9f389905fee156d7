"""Device-resident jitted phase engine for the Dragonfly simulator.

``SimParams.backend = "jax"`` routes the score -> spray -> feedback
fixed point -> observables pipeline of ``run_phase`` through ONE jitted
function whose feedback loop is a ``lax.scan`` — iterations never
touch the host, and compile time no longer scales with
``route_feedback_iters``.  Three things make the path device-resident:

  * **In-graph scoring.** The host no longer materializes ``score0``
    for the jax path: the loop-invariant score base (queue-estimate
    gather + hop latency + bias/notification terms) is computed inside
    the graph from the per-link estimate vector, so the expensive
    [n, ncand, hops] gather runs on the device instead of in NumPy: on
    the chip in `path_gather_pallas`, the link table held in VMEM
    (`uses_path_gather`), elsewhere in XLA.

  * **Plan-pinned device buffers.** When a :class:`PhasePlan` is
    replayed, its phase-invariant tensors are transferred once and
    pinned on the plan as a `_DevicePlan` (``plan.device_bundle``),
    which also holds the full-size buffers of the plan's last phase;
    the plan cache key already covers topology spec + fault epoch +
    notify epoch, so a stale bundle cannot outlive its plan.  Per phase
    only the small per-link state, the background-flow slivers (written
    into the donated buffers in place) and the Gumbel noise block move
    host->device.  The noise block is not donated: no output has its
    [iters, n, ncand] shape, so XLA could never reuse its buffer (a TPU
    v5e reports such a donation as unusable).

  * **Stable shapes.** Background flows redraw candidates per phase, so
    the (link, flow-cand) pair-list length P changes every phase.  One
    rule, `_pair_layout`, pads pair lists to coarse buckets with
    zero-weight entries (mask 0.0, link 0 — exact no-ops under the
    segment sum), and `batch_signature` keys on it too, so steady-state
    phases reuse one compiled executable.

Fault candidate masks and congestion-notification penalties are both
consumed in-graph (the mask as a ``where(+inf)`` before every softmin,
the penalty folded into the per-link estimate by the caller), so
faulted / notification-active phases no longer fall back to numpy.

``fixed_point_jax_batch`` evaluates SEVERAL phases (one per simulator)
through a single ``jax.vmap``-ed dispatch when their shapes/statics
agree — the entry point ``run_phase_batch`` / the tenancy lockstep
driver use to batch whole sweep columns.

RNG parity: ALL randomness (background draws, candidate paths, phantom
noise, per-iteration Gumbel spray noise) is drawn on the host from the
simulator's NumPy generator — the jitted pipeline is deterministic in
its inputs, so the jax backend consumes the RNG stream draw-for-draw
like the NumPy backend and matches it within float32 tolerance
(pinned at rtol=2e-2 for the Eq.(2) times in the tests).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.compat.runtime import on_tpu, resolve_pallas_kernel
from repro.kernels.path_gather.path_gather import (
    LANES, index_rows, path_gather_pallas)
from repro.kernels.segment_sum.ref import segment_sum_ref
from repro.kernels.segment_sum.segment_sum import (
    BLOCK, dense_grid_steps, segment_sum_pallas, segment_sum_sorted_pallas,
    sorted_grid_steps, sorted_schedule)

#: diagnostics: executed-pipeline counters ("single"/"batched" jitted
#: dispatches).  Tests, perf_sim and chip_smoke.py assert on deltas to
#: prove the jitted pipeline really ran.
PIPELINE_CALLS = {"single": 0, "batched": 0}

#: host<->device traffic of the pipeline, counted always (a few integer
#: adds a phase).  ``h2d_copies``/``h2d_bytes``: host buffers handed to
#: the device for a phase's inputs, with their bytes as handed (a
#: float64 buffer converted on the device counts 8 B an element);
#: ``d2h_bytes``: the outputs fetched back.  The one-off pinning of a
#: plan's invariant tensors (`_device_plan`) is not counted, so a plan's
#: first phase counts as every other.
TRANSFER = {"h2d_copies": 0, "h2d_bytes": 0, "d2h_bytes": 0}

#: Pallas segment-sum calls of the pipeline, counted always from each
#: phase's statics and shapes: ``sorted_calls`` (plan-reused heads,
#: `segment_sum_sorted_pallas`), ``dense_calls`` (the NIC sum, the
#: unsorted tails and planless pair lists, `segment_sum_pallas`) and
#: their ``grid_steps``; ``head_pairs`` and ``head_pad_pairs``, the real
#: and the zero-mask pairs of each dispatched sorted head (`_head_len`).
#: Nothing counts where the kernel is off.
SEGSUM = {"sorted_calls": 0, "dense_calls": 0, "grid_steps": 0,
          "head_pairs": 0, "head_pad_pairs": 0}

#: slots of the pipeline's gathers of a per-link vector through the
#: candidates' link ids ``safe`` (the score's, each feedback
#: iteration's, and the observables' two), counted always from each
#: phase's statics and shapes: ``kernel_slots`` where
#: `path_gather_pallas` does them, ``xla_slots`` where XLA's gather does.
GATHER = {"kernel_slots": 0, "xla_slots": 0}

#: most links for which the pipeline gathers through ``safe`` with
#: `path_gather_pallas`: its work grows with the table's rows of 128
#: links, XLA's gather's does not (`uses_path_gather`).  On a TPU v5e
#: 5,760,768 ids took the kernel 14.4 / 31.6 / 47.0 / 62.6 ms from
#: 56,448 / 131,072 / 196,608 / 262,144 links and XLA's gather 42.2 ms,
#: a crossover near 1,375 table rows; the threshold is 1,280 rows.
PATH_GATHER_MAX_LINKS = 1_280 * LANES

#: pair-list padding buckets (docs/performance.md).  Plan-reused phases
#: only redraw the ~bg_flows_per_phase background rows, so their pair
#: tail is padded to a small bucket; planless phases redraw everything
#: and get a coarse bucket.  Bigger buckets = fewer distinct compiled
#: shapes at the cost of a few zero-weight pairs per segment sum.  The
#: default 16 background flows x 6 candidates x 8 hops make at most 768
#: pairs (400-540 in practice, straddling 512), so one 1024 bucket keeps
#: every plan-reused phase on one executable.
_PAIR_BUCKET_PLAN = 1024
_PAIR_BUCKET_FULL = 4096

#: block width of the sorted head.  The pinned sorted pair list is
#: padded to a multiple of this (zero-mask entries on the last link), so
#: the blocked cumsum needs no remainder handling and the sorted
#: kernel's pair blocks never reach into the unsorted tail.
_HEAD_BLOCK = BLOCK
#: a head of P >= _HEAD_BUCKET_FROM pairs is padded to a multiple of
#: 2**(floor(log2 P) - _HEAD_BUCKET_SHIFT), a smaller one to a
#: `_HEAD_BLOCK` multiple (`_head_len`)
_HEAD_BUCKET_FROM = 1 << 15
_HEAD_BUCKET_SHIFT = 3


def kernel_mode(params) -> tuple:
    """(use_kernel, interpret) statics of the pipeline's segment sum.

    The Pallas kernel runs compiled on a TPU.  Interpret mode happens
    only where ``pallas_kernel="on"`` forces the kernel off the chip —
    the parity-testing path."""
    use_kernel = resolve_pallas_kernel(params.pallas_kernel)
    return use_kernel, use_kernel and not on_tpu()


def uses_path_gather(use_kernel: bool, n_links: int) -> bool:
    """Does the pipeline gather through ``safe`` with the Pallas kernel?
    Where the segment sum's kernel runs and the link table is at most
    `PATH_GATHER_MAX_LINKS`.  The pipeline then takes ``safe`` as its
    [rows, 128] `path_view`, else as the [n, ncand, hops] ids."""
    return use_kernel and n_links <= PATH_GATHER_MAX_LINKS


def _padded_len(n: int, bucket: int) -> int:
    return -(-max(int(n), 1) // bucket) * bucket


def _head_len(p: int) -> int:
    """Padded length of a plan's sorted head of ``p`` pairs.  From
    32,768 pairs on, the next multiple of a bucket that grows with
    ``p``, an eighth of its power of two: placements of one job size,
    whose pair counts differ by a few percent, then share one compiled
    shape, for a pad under 1/8 of the pairs.  Smaller heads keep
    `_HEAD_BLOCK` multiples."""
    p = max(int(p), 1)
    if p < _HEAD_BUCKET_FROM:
        return _padded_len(p, _HEAD_BLOCK)
    return _padded_len(p, 1 << (p.bit_length() - 1 - _HEAD_BUCKET_SHIFT))


# --------------------------------------------------------------- pipeline
def _phase_pipeline(safe, validf, hops, is_nonmin, cand_mask, est_queue_s,
                    link_queue_s, hl_rows, bias_rows, posinf, neginf,
                    t_rows, noise_scale, gnoise, size_all, cap_window,
                    nic_ids, pair_links, pair_fc, pair_mask, seg_off,
                    sched, window_s, feedback_rho0, rho_threshold,
                    queue_delay_ns, qwait_fraction, stall_gain,
                    nic_latency_ns, hop_latency_ns, *, n_links: int,
                    use_kernel: bool, interpret: bool, p_sorted: int):
    """One phase: score -> spray -> lax.scan feedback -> observables.

    Pure in its arguments; statics select the segment-sum implementation
    (Pallas vs jax.ops.segment_sum) and fix the bin count; the loop
    count is ``gnoise``'s leading dimension.
    ``cand_mask`` may be None (healthy machine) — the mask branch then
    never enters the graph.  ``pair_mask`` zeroes the bucket-padding
    entries so they are exact no-ops in every accumulation.

    ``p_sorted``/``seg_off``/``sched``: the first ``p_sorted`` pair
    entries are pre-sorted by link id on the host (the plan-pinned app
    pairs), with ``seg_off`` their [n_links+1] segment offsets and
    ``sched`` the sorted kernel's visit list built from them.  On the
    Pallas path that head goes through `segment_sum_sorted_pallas`, each
    link block reading only its own pair blocks; otherwise it reduces
    via cumsum-diff, which XLA CPU runs ~5x faster than the scatter-add
    lowering of `segment_sum`.  The unsorted tail (the per-phase
    background sliver), the NIC sum and planless pair lists
    (``p_sorted == 0``) scatter-add: the dense kernel, or
    `segment_sum`.

    ``safe`` is the candidates' [n, ncand, hops] link ids, or their
    [rows, 128] `path_view` where `uses_path_gather` holds: the four
    gathers of a per-link vector through them (`link_gather`) then run
    in `path_gather_pallas`, with the link table in VMEM.
    """
    def link_gather(table):
        """``table[safe]``, [n, ncand, hops]."""
        with jax.named_scope("path_gather"):
            if safe.ndim == validf.ndim:
                return table[safe]
            return from_path_view(
                path_gather_pallas(table, safe, interpret=interpret),
                validf.shape)

    def seg_sum(vals, ids):
        with jax.named_scope("segsum"):
            if use_kernel:
                return segment_sum_pallas(vals, ids, n_links,
                                          interpret=interpret)
            return segment_sum_ref(vals, ids, n_links)

    def pair_sum(vals):
        if not p_sorted:
            return seg_sum(vals, pair_links)
        with jax.named_scope("segsum"):
            if use_kernel:
                # the whole list goes in: the schedule reads only the head
                out = segment_sum_sorted_pallas(vals, pair_links, sched,
                                                n_links, interpret=interpret)
            else:
                # blocked prefix sum over the sorted head: per-block
                # cumsums vectorize across rows where XLA CPU's 1-D
                # cumsum does not, and only the [n_links+1] boundary
                # prefixes materialize.
                nb = p_sorted // _HEAD_BLOCK
                within = jnp.cumsum(
                    vals[:p_sorted].reshape(nb, _HEAD_BLOCK), axis=1)
                base = jnp.concatenate([jnp.zeros(1, vals.dtype),
                                        jnp.cumsum(within[:, -1])])
                i, j = seg_off // _HEAD_BLOCK, seg_off % _HEAD_BLOCK
                w_in = within[jnp.minimum(i, nb - 1), jnp.maximum(j - 1, 0)]
                pref = base[i] + jnp.where(j > 0, w_in, 0.0)
                out = pref[1:] - pref[:-1]
        if vals.shape[0] > p_sorted:
            out = out + seg_sum(vals[p_sorted:], pair_links[p_sorted:])
        return out

    # loop-invariant score base, in-graph (the hoisted scorer of the
    # numpy fast path: estimate gather + hop latency + bias terms)
    with jax.named_scope("score"):
        base = (link_gather(est_queue_s) * validf).sum(axis=-1) \
            + hl_rows[:, None] * hops
        score0 = base + jnp.where(is_nonmin[None, :], bias_rows[:, None],
                                  0.0)
        score0 = jnp.where(posinf[:, None] & is_nonmin[None, :], jnp.inf,
                           score0)
        score0 = jnp.where(neginf[:, None] & ~is_nonmin[None, :], jnp.inf,
                           score0)
        if cand_mask is not None:
            # fault path: candidates crossing dead links spray exactly
            # zero (all-False rows — stranded flows — spray nowhere)
            score0 = jnp.where(cand_mask, score0, jnp.inf)

    # a flow cannot inject more than its NIC moves in the window
    with jax.named_scope("loads"):
        size_inst = jnp.minimum(size_all, cap_window[nic_ids])
        nic_load = seg_sum(size_inst, nic_ids)

    @jax.named_scope("spray")
    def spray(score, g):
        s = score + g * noise_scale
        s = jnp.where(jnp.isfinite(s), s, jnp.inf)
        smin = s.min(axis=1, keepdims=True)
        smin = jnp.where(jnp.isfinite(smin), smin, 0.0)
        z = jnp.exp(-(s - smin) / t_rows[:, None])
        tot = z.sum(axis=1, keepdims=True)
        tot = jnp.where(tot <= 0, 1.0, tot)
        return z / tot

    @jax.named_scope("loads")
    def loads(w):
        vals = (size_inst[:, None] * w).reshape(-1)[pair_fc] * pair_mask
        return pair_sum(vals) + nic_load

    w0 = spray(score0, gnoise[0])

    def body(carry, g):
        w, load_i = carry
        rho_fb = load_i / cap_window
        extra = jnp.maximum(0.0, rho_fb - feedback_rho0) * window_s
        score = score0 + (link_gather(extra) * validf).sum(axis=-1)
        w = 0.5 * (w + spray(score, g))
        return (w, loads(w)), None

    # scan (not fori_loop + dynamic_index): the per-iteration noise block
    # arrives as a scanned input, so XLA skips the in-loop gather-copy of
    # gnoise[it]; compile time still does not scale with n_spray
    with jax.named_scope("feedback"):
        (w, load_i), _ = jax.lax.scan(body, (w0, loads(w0)), gnoise[1:])

    with jax.named_scope("loads"):
        load_q = pair_sum((size_all[:, None] * w).reshape(-1)[pair_fc]
                          * pair_mask)

    # --- observables: per-flow (L_us, s) ------------------------------
    with jax.named_scope("observables"):
        rho = load_i / cap_window
        rho_path = link_gather(rho) * validf        # [n, ncand, hops]
        excess = jnp.maximum(0.0, rho_path - rho_threshold)
        qdelay_ns = queue_delay_ns * excess.sum(axis=-1)
        qwait_ns = (link_gather(link_queue_s) * validf).sum(axis=-1) \
            * qwait_fraction * 1e9
        lat_ns_cand = 2.0 * nic_latency_ns + hops * hop_latency_ns \
            + qdelay_ns + qwait_ns
        lat_us = (lat_ns_cand * w).sum(axis=-1) / 1e3
        rho_nic = rho[nic_ids]
        rho_bneck = jnp.maximum(rho_path.max(axis=-1), rho_nic[:, None])
        s_cand = stall_gain * jnp.maximum(0.0, rho_bneck - rho_threshold)
        s_flit = (s_cand * w).sum(axis=-1)
    return w, rho, load_q, lat_us, s_flit


class _Statics(NamedTuple):
    """`_phase_pipeline`'s static arguments for one phase."""
    n_links: int
    use_kernel: bool
    interpret: bool
    p_sorted: int


@functools.lru_cache(maxsize=None)
def _jitted_pipeline(statics: _Statics, batched: bool):
    """Compiled pipeline per (statics, batched) combo.

    ``batched`` wraps the core in ``jax.vmap`` over a stacked leading
    phase axis — scalars ride along as [B] vectors, and an absent
    ``cand_mask`` (None) is an empty pytree.
    """
    core = functools.partial(_phase_pipeline, **statics._asdict())
    return jax.jit(jax.vmap(core) if batched else core)


# ------------------------------------------------------- input preparation
def _put(a, dtype=None):
    """Hand host buffer ``a`` to the device as ``dtype`` (its own dtype
    when None), counted in `TRANSFER` as one of a phase's input copies."""
    a = np.asarray(a)
    TRANSFER["h2d_copies"] += 1
    TRANSFER["h2d_bytes"] += a.nbytes
    return jnp.asarray(a, dtype=dtype)


def _f32(a):
    return _put(a, jnp.float32)


def _i32(a):
    return _put(a, jnp.int32)


@dataclasses.dataclass(eq=False)
class _DevicePlan:
    """A PhasePlan's tensors pinned on the device (`_device_plan`): the
    heads (``safe`` to ``pair_mask``, the pairs link-sorted and padded to
    ``p_sorted``), the sorted head's ``seg_off`` and ``sched``; and
    ``bufs``, the last phase's `buffers`, None while they are donated."""
    safe: jax.Array
    validf: jax.Array
    hops: jax.Array
    nic_ids: jax.Array
    pair_links: jax.Array
    pair_fc: jax.Array
    pair_mask: jax.Array
    seg_off: jax.Array
    sched: jax.Array
    p_sorted: int
    bufs: tuple | None = None

    def buffers(self, tails: tuple, view: bool) -> tuple:
        """A phase's (safe, validf, hops, nic_ids, pair_links, pair_fc,
        pair_mask): the heads followed by ``tails`` (its background rows
        and padded pairs; none without background rows), ``safe`` as its
        `path_view` with ``view``.  Where the last phase's buffers agree
        in rows, pairs and view-ness, only the tails are written into
        them, donated: the pinned heads are never re-copied."""
        n_app = self.validf.shape[0]
        n_rows = n_app + (tails[0].shape[0] if tails else 0)
        n_pairs = self.p_sorted + (tails[4].shape[0] if tails else 0)
        heads = (self.safe, self.validf, self.hops, self.nic_ids,
                 self.pair_links, self.pair_fc, self.pair_mask)
        bufs = self.bufs
        if (bufs is not None and bufs[1].shape[0] == n_rows
                and bufs[4].shape[0] == n_pairs
                and (bufs[0].ndim == 2) == view):
            if tails:
                self.bufs = None         # donation consumes the olds
                bufs = _tail_writer(n_app, self.p_sorted, view)(bufs, tails)
        else:
            bufs = tuple(jnp.concatenate([h, t])
                         for h, t in zip(heads, tails)) if tails else heads
            if view:
                bufs = (_path_view(bufs[0]), *bufs[1:])
        self.bufs = bufs
        return bufs


def _device_plan(plan, n_links: int) -> _DevicePlan:
    """Pin a PhasePlan's phase-invariant tensors on device, once, as
    ``plan.device_bundle``: the bundle lives exactly as long as its plan.

    The pair list is pinned SORTED BY LINK ID (a host-side argsort, paid
    once per plan), padded to its `_pair_layout` head with zero-mask
    entries on the last link (sort order survives, padded values are
    exactly 0.0), with its segment offsets alongside and the sorted
    kernel's visit list (`sorted_schedule`) built from them on the host:
    both of the pipeline's head reductions, the Pallas kernel's and the
    blocked cumsum-diff, need sorted block-aligned segments.  The plan's
    own (host) arrays keep original order: numpy-backend parity is
    untouched.  These one-off uploads stay out of `TRANSFER`, which
    counts a phase's own."""
    dev = plan.device_bundle
    if dev is None:
        pl = np.asarray(plan.pair_links)
        order = np.argsort(pl, kind="stable")
        p_pad, _ = _pair_layout(plan, pl.shape[0])
        links = np.full(p_pad, n_links - 1, dtype=np.int32)
        links[:pl.shape[0]] = pl[order]
        fc = np.zeros(p_pad, dtype=np.int32)
        fc[:pl.shape[0]] = np.asarray(plan.pair_fc)[order]
        mask = np.zeros(p_pad, dtype=np.float32)
        mask[:pl.shape[0]] = 1.0
        off = np.zeros(n_links + 1, dtype=np.int64)
        np.cumsum(np.bincount(links, minlength=n_links), out=off[1:])
        dev = _DevicePlan(
            safe=jnp.asarray(plan.safe, dtype=jnp.int32),
            validf=jnp.asarray(plan.valid, dtype=jnp.float32),
            hops=jnp.asarray(plan.hops, dtype=jnp.float32),
            nic_ids=jnp.asarray(plan.nic_ids, dtype=jnp.int32),
            pair_links=jnp.asarray(links),
            pair_fc=jnp.asarray(fc),
            pair_mask=jnp.asarray(mask),
            seg_off=jnp.asarray(off, dtype=jnp.int32),
            sched=jnp.asarray(sorted_schedule(off)),
            p_sorted=p_pad)
        plan.device_bundle = dev
    return dev


@functools.lru_cache(maxsize=None)
def _tail_writer(n_app: int, p_head: int, view: bool):
    """Jitted donated-buffer tail update: writes the per-phase background
    rows/pairs into the pinned full-size buffers IN PLACE (the buffers
    are donated, so XLA aliases them instead of copying the plan-pinned
    head every phase).  With ``view`` the first buffer is ``safe``'s
    `path_view`, and the tail's ids go to their places there."""
    def write(bufs, tails):
        safe = (_write_view(bufs[0], tails[0], n_app) if view
                else bufs[0].at[n_app:].set(tails[0]))
        rows = tuple(b.at[n_app:].set(t)
                     for b, t in zip(bufs[1:4], tails[1:4]))
        pairs = tuple(b.at[p_head:].set(t)
                      for b, t in zip(bufs[4:], tails[4:]))
        return (safe, *rows, *pairs)
    return jax.jit(write, donate_argnums=(0,))


def path_view(safe):
    """The candidates' link ids [n, ncand, hops] as the [rows, 128] int32
    view `path_gather_pallas` reads (`index_rows` rows, zero-padded), in
    the order of their n-minor tiles: row ``(c * nb + b) * hops + h``
    holds the ids of rows ``b * 128`` to ``b * 128 + 127`` at candidate
    ``c``, hop ``h``.  XLA on a TPU lays the pipeline's [n, ncand, 8]
    arrays out n-minor in (8, 128) tiles, so the gathered view's tiles
    are theirs and `from_path_view` costs no relayout."""
    n, nc, nh = safe.shape
    nb = -(-n // LANES)
    ids = jnp.pad(safe.astype(jnp.int32), ((0, nb * LANES - n), (0, 0),
                                          (0, 0)))
    ids = ids.reshape(nb, LANES, nc, nh).transpose(2, 0, 3, 1)
    ids = ids.reshape(-1, LANES)
    return jnp.pad(ids, ((0, index_rows(ids.size) - ids.shape[0]), (0, 0)))


def from_path_view(out, shape):
    """The [n, ncand, hops] array of ``shape`` whose `path_view` is
    ``out``."""
    n, nc, nh = shape
    nb = -(-n // LANES)
    out = out[:nc * nb * nh].reshape(nc, nb, nh, LANES)
    return out.transpose(1, 3, 0, 2).reshape(nb * LANES, nc, nh)[:n]


def _write_view(view, tail, n_app: int):
    """``view`` (a `path_view` of ``n_app + len(tail)`` rows of ids) with
    ``tail``'s ids written as its rows ``n_app`` on."""
    n_bg, nc, nh = tail.shape
    nb = -(-(n_app + n_bg) // LANES)
    r = n_app + np.arange(n_bg)[:, None, None]
    rows = (np.arange(nc)[:, None] * nb + r // LANES) * nh + np.arange(nh)
    lanes = np.broadcast_to(r % LANES, rows.shape)
    return view.at[rows, lanes].set(tail)


_path_view = jax.jit(path_view)


def _pad_pairs(links: np.ndarray, fc: np.ndarray, pad_to: int):
    """Host-side bucket padding of a pair-list tail.

    Padding entries carry mask 0.0 and link/fc 0: the masked value is
    exactly 0.0, so scatter-adding it into bin 0 is a bitwise no-op —
    shapes stabilize without perturbing any segment sum."""
    n = links.shape[0]
    pl = np.zeros(pad_to, dtype=np.int32)
    pl[:n] = links
    pf = np.zeros(pad_to, dtype=np.int32)
    pf[:n] = fc
    pm = np.zeros(pad_to, dtype=np.float32)
    pm[:n] = 1.0
    return _put(pl), _put(pf), _put(pm)


def _pair_layout(plan, n_pairs: int) -> tuple[int, int]:
    """(head, tail): the padded lengths of a phase's link-sorted pair
    head and its unsorted tail, from its ``plan`` (None: planless) and
    its ``n_pairs`` (link, flow-cand) pairs.

    A plan's own pairs form the head, padded by `_head_len`; the rest,
    its background flows' pairs, pad to `_PAIR_BUCKET_PLAN` buckets
    (none where there are none).  A planless phase has no head and pads
    all its pairs to `_PAIR_BUCKET_FULL` buckets."""
    if plan is None:
        return 0, _padded_len(n_pairs, _PAIR_BUCKET_FULL)
    p_app = int(plan.pair_links.shape[0])
    n_bg = n_pairs - p_app
    return (_head_len(p_app),
            _padded_len(n_bg, _PAIR_BUCKET_PLAN) if n_bg else 0)


def _prepare_inputs(sim, ctx: dict):
    """ctx (from `_phase_begin`) -> (pipeline inputs, `_Statics`)."""
    p = sim.params
    tp = sim.topo
    plan = ctx["plan"]
    n_links = int(tp.n_links)
    use_kernel, interpret = kernel_mode(p)
    view = uses_path_gather(use_kernel, n_links)
    head, tail = _pair_layout(plan, ctx["pair_links"].shape[0])
    if plan is not None:
        dev = _device_plan(plan, n_links)
        tails = ()
        if ctx["safe"].shape[0] > ctx["n_app"]:   # background rows ride along
            sl = slice(ctx["n_app"], None)
            p_app = plan.pair_links.shape[0]
            tails = (_i32(ctx["safe"][sl]), _f32(ctx["valid"][sl]),
                     _f32(ctx["hops"][sl]), _i32(ctx["nic_ids"][sl]),
                     *_pad_pairs(ctx["pair_links"][p_app:],
                                 ctx["pair_fc"][p_app:], tail))
        (safe, validf, hops, nic_ids,
         pair_links, pair_fc, pair_mask) = dev.buffers(tails, view)
        seg_off, sched = dev.seg_off, dev.sched
    else:
        safe = _i32(ctx["safe"])
        if view:
            safe = _path_view(safe)
        validf = _f32(ctx["valid"])
        hops = _f32(ctx["hops"])
        nic_ids = _i32(ctx["nic_ids"])
        pair_links, pair_fc, pair_mask = _pad_pairs(
            ctx["pair_links"], ctx["pair_fc"], tail)
        seg_off = jnp.zeros(n_links + 1, dtype=jnp.int32)
        sched = jnp.zeros(3, dtype=jnp.int32)

    cm = ctx["cand_mask"]
    inputs = (
        safe, validf, hops, _put(ctx["is_nonmin"]),
        None if cm is None else _put(cm),
        _f32(ctx["est_queue_s"]), _f32(sim.link_queue_s),
        _f32(ctx["hl_rows"]), _f32(ctx["bias_rows"]),
        _put(ctx["posinf"]), _put(ctx["neginf"]),
        _f32(ctx["t_rows"]), _f32(ctx["noise_scale"]),
        _put(np.asarray(ctx["gnoise"], dtype=np.float32)),
        _f32(ctx["size_all"]), _f32(ctx["cap_window"]), nic_ids,
        pair_links, pair_fc, pair_mask, seg_off, sched,
        _f32(ctx["window_s"]), _f32(p.feedback_rho0),
        _f32(p.rho_threshold), _f32(p.queue_delay_ns),
        _f32(p.qwait_fraction), _f32(p.stall_gain),
        _f32(tp.nic_latency_ns), _f32(tp.hop_latency_ns),
    )
    statics = _Statics(n_links, use_kernel, interpret, head)
    n_spray = int(ctx["gnoise"].shape[0])
    # the score's and the observables' two, one a feedback iteration
    GATHER["kernel_slots" if view else "xla_slots"] += \
        (3 + n_spray - 1) * int(validf.size)
    if use_kernel:
        _count_segsum(statics, n_spray, int(pair_links.shape[0]),
                      int(nic_ids.shape[0]),
                      0 if plan is None else int(plan.pair_links.shape[0]))
    return inputs, statics


def _count_segsum(statics: _Statics, n_spray: int, n_pairs: int,
                  n_rows: int, p_head: int):
    """Add one phase's Pallas segment sums to `SEGSUM`: the NIC sum over
    ``n_rows``, and each of the ``n_spray + 1`` pair reductions (the
    first spray, each feedback iteration, ``load_q``) over the sorted
    head and the unsorted rest of ``n_pairs``; and the head's ``p_head``
    real pairs with the zero-mask pairs that pad it, once a phase."""
    n_links, p_sorted = statics.n_links, statics.p_sorted
    reductions = n_spray + 1
    steps = dense_grid_steps(n_rows, n_links)
    dense = 1
    if p_sorted:
        SEGSUM["sorted_calls"] += reductions
        SEGSUM["head_pairs"] += p_head
        SEGSUM["head_pad_pairs"] += p_sorted - p_head
        steps += reductions * sorted_grid_steps(p_sorted, n_links)
    if n_pairs > p_sorted:
        dense += reductions
        steps += reductions * dense_grid_steps(n_pairs - p_sorted, n_links)
    SEGSUM["dense_calls"] += dense
    SEGSUM["grid_steps"] += steps


def batch_signature(sim, ctx: dict) -> tuple:
    """Hashable key: phases with equal keys (shapes + statics + mask
    presence) can share one vmapped dispatch."""
    return (int(sim.topo.n_links), int(ctx["gnoise"].shape[0]),
            *kernel_mode(sim.params), tuple(ctx["safe"].shape),
            _pair_layout(ctx["plan"], ctx["pair_links"].shape[0]),
            ctx["cand_mask"] is not None)


# ------------------------------------------------------------ entry points
def _dispatch(sim, phase: int, fn, inputs):
    """``fn(*inputs)`` in the stage ``device_wait``, which under
    ``profile_stages`` also waits for its outputs."""
    with sim.stage("device_wait", phase):
        out = fn(*inputs)
        if sim.params.profile_stages:
            jax.block_until_ready(out)
    return out


def _fetch(sim, phase: int, out) -> tuple:
    """The outputs copied to host float64, in the stage ``fetch``."""
    TRANSFER["d2h_bytes"] += sum(o.nbytes for o in out)
    with sim.stage("fetch", phase):
        return tuple(np.asarray(o, dtype=np.float64) for o in out)


def fixed_point_jax(sim, ctx: dict):
    """One phase on device; float64 numpy outputs (kernel contract:
    (w, rho, load_q, lat_us, s_flit), same as `_fixed_point_numpy`).

    Its stages, inside the simulator's ``fixed_point``: ``transfer``
    (every input handed to the device), ``device_wait`` (the dispatch
    until the outputs are ready) and ``fetch`` (the outputs copied to
    host float64)."""
    phase = ctx["phase"]
    with sim.stage("transfer", phase):
        inputs, statics = _prepare_inputs(sim, ctx)
    fn = _jitted_pipeline(statics, batched=False)
    out = _dispatch(sim, phase, fn, inputs)
    PIPELINE_CALLS["single"] += 1
    return _fetch(sim, phase, out)


def fixed_point_jax_batch(batch):
    """Many phases, ONE vmapped dispatch.

    ``batch``: [(sim, ctx)] whose `batch_signature`s agree (the caller
    groups).  Returns one kernel-output tuple per entry, batch order.
    Cells keep their own simulators/RNG streams — batching changes the
    dispatch, not the draws, so results match per-cell dispatch within
    float32 reassociation noise.

    The stages ``transfer``, ``device_wait`` and ``fetch`` are recorded
    once per dispatch, on the batch's first simulator and in its phase."""
    sim, phase = batch[0][0], batch[0][1]["phase"]
    with sim.stage("transfer", phase):
        prepped = [_prepare_inputs(s, ctx) for s, ctx in batch]
        stacked = jax.tree.map(lambda *col: jnp.stack(col),
                               *(inputs for inputs, _ in prepped))
    fn = _jitted_pipeline(prepped[0][1], batched=True)
    outs = _fetch(sim, phase, _dispatch(sim, phase, fn, stacked))
    PIPELINE_CALLS["batched"] += 1
    return [tuple(o[b] for o in outs) for b in range(len(batch))]
