"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rmsnorm.ops import rmsnorm_op
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.ssd_scan.ops import ssd_scan_op
from repro.models.mamba2 import ssd_chunked

RNG = np.random.default_rng(0)


def _mk(shape, dtype):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


@pytest.mark.parametrize("B,H,Hkv,S,hd,bq,bk", [
    (1, 2, 2, 128, 32, 64, 64),      # MHA
    (2, 4, 2, 256, 64, 64, 128),     # GQA
    (1, 8, 1, 128, 32, 32, 64),      # MQA (paligemma-style)
    (2, 2, 2, 192, 16, 64, 64),      # non-pow2 seq
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(B, H, Hkv, S, hd, bq, bk, causal):
    q = _mk((B, H, S, hd), jnp.float32)
    k = _mk((B, Hkv, S, hd), jnp.float32)
    v = _mk((B, Hkv, S, hd), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


def test_flash_attention_bf16():
    q = _mk((1, 2, 128, 64), jnp.bfloat16)
    k = _mk((1, 2, 128, 64), jnp.bfloat16)
    v = _mk((1, 2, 128, 64), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 32, 2, 8, 8, 8),
    (2, 64, 3, 8, 16, 16),
    (1, 128, 4, 16, 32, 32),
])
def test_ssd_kernel_matches_model_oracle(B, S, H, P, N, chunk):
    x = _mk((B, S, H, P), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.1, 1.0, (B, S, H)), jnp.float32)
    a_log = jnp.asarray(RNG.uniform(-1, 1, (H,)), jnp.float32)
    bm = _mk((B, S, H, N), jnp.float32)
    cm = _mk((B, S, H, N), jnp.float32)
    y_ref, f_ref = ssd_chunked(x, dt, a_log, bm, cm, chunk)
    y_k, f_k = ssd_scan_op(x, dt, a_log, bm, cm, chunk, force_kernel=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_ref),
                               rtol=3e-4, atol=3e-4)


def test_ssd_kernel_state_passing():
    B, S, H, P, N, chunk = 1, 64, 2, 8, 8, 16
    x = _mk((B, S, H, P), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.1, 1.0, (B, S, H)), jnp.float32)
    a_log = jnp.asarray(RNG.uniform(-1, 1, (H,)), jnp.float32)
    bm = _mk((B, S, H, N), jnp.float32)
    cm = _mk((B, S, H, N), jnp.float32)
    y_full, _ = ssd_scan_op(x, dt, a_log, bm, cm, chunk, force_kernel=True)
    y1, s1 = ssd_scan_op(x[:, :32], dt[:, :32], a_log, bm[:, :32],
                         cm[:, :32], chunk, force_kernel=True)
    y2, _ = ssd_scan_op(x[:, 32:], dt[:, 32:], a_log, bm[:, 32:],
                        cm[:, 32:], chunk, init_state=s1, force_kernel=True)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([y1, y2], 1)), np.asarray(y_full),
        rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("shape", [(8, 64), (3, 5, 128), (2, 7, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_kernel_matches_ref(shape, dtype):
    x = _mk(shape, dtype)
    g = _mk((shape[-1],), jnp.float32)
    out = rmsnorm_op(x, g, force_kernel=True)
    ref = rmsnorm_ref(x, g)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


# --------------------------------------------------------------------------
# segment_sum: the Dragonfly fast path's link-load scatter-add.
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n,segs,bp,bs", [
    (1000, 300, 256, 128),       # multi-block both axes
    (257, 64, 256, 64),          # ragged pair tail
    (64, 1000, 64, 256),         # more segments than pairs
    (5, 3, 1024, 512),           # tiny, single block
    (3000, 2500, 1024, 1024),    # the default (chip-tiled) blocks
])
def test_segment_sum_kernel_matches_ref(n, segs, bp, bs):
    from repro.kernels.segment_sum import segment_sum_ref
    from repro.kernels.segment_sum.segment_sum import segment_sum_pallas

    ids = jnp.asarray(RNG.integers(0, segs, size=n), jnp.int32)
    vals = jnp.asarray(RNG.random(n), jnp.float32)
    out = segment_sum_pallas(vals, ids, segs, block_pairs=bp,
                             block_segs=bs, interpret=True)
    ref = segment_sum_ref(vals, ids, segs)
    assert out.shape == (segs,)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_segment_sum_kernel_vmaps_lane_by_lane():
    """Under vmap (the lockstep batch) each lane is its own segment sum,
    batched and unbatched ids alike."""
    import jax

    from repro.kernels.segment_sum import segment_sum_ref
    from repro.kernels.segment_sum.segment_sum import segment_sum_pallas

    ids = jnp.asarray(RNG.integers(0, 300, size=(3, 700)), jnp.int32)
    vals = jnp.asarray(RNG.random((3, 700)), jnp.float32)

    def kern(v, i):
        return segment_sum_pallas(v, i, 300, interpret=True)

    def ref(v, i):
        return segment_sum_ref(v, i, 300)

    np.testing.assert_allclose(np.asarray(jax.vmap(kern)(vals, ids)),
                               np.asarray(jax.vmap(ref)(vals, ids)),
                               rtol=1e-5, atol=1e-5)
    shared = jax.vmap(kern, in_axes=(0, None))(vals, ids[0])
    np.testing.assert_allclose(
        np.asarray(shared),
        np.asarray(jax.vmap(ref, in_axes=(0, None))(vals, ids[0])),
        rtol=1e-5, atol=1e-5)


def test_segment_sum_empty_and_untouched_segments():
    from repro.kernels.segment_sum import segment_sum_pallas, segment_sum_ref

    ids = jnp.asarray([2, 2, 5], jnp.int32)
    vals = jnp.asarray([1.0, 2.0, 4.0], jnp.float32)
    out = segment_sum_pallas(vals, ids, 8, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), [0, 0, 3.0, 0, 0, 4.0, 0, 0], atol=1e-7)
    # the reference keeps the same contract
    out2 = segment_sum_ref(vals, ids, 8)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(out),
                               atol=1e-7)


# --------------------------------------------------------------------------
# sorted segment_sum: a plan's link-sorted pairs, each link block visiting
# only its own pair blocks (scalar-prefetched schedule, 1024-wide blocks).
# --------------------------------------------------------------------------
def _sorted_pairs(case, rng):
    """(sorted ids, values, n_links) of one layout the schedule must
    cover; values are random and nonzero except where padding says."""
    if case == "empty_segment_blocks":          # blocks 1 and 2 hold none
        ids, n_links = np.r_[rng.integers(0, 500, 300),
                             rng.integers(3500, 4096, 900)], 4096
    elif case == "link_crosses_pair_block":     # link 100: pairs 500-1999
        ids, n_links = np.r_[rng.integers(0, 100, 500), np.full(1500, 100),
                             rng.integers(101, 2000, 600)], 2000
    elif case == "pair_block_straddles_segment_blocks":
        ids, n_links = np.r_[rng.integers(0, 1000, 1500),
                             rng.integers(1000, 1050, 1024),
                             rng.integers(1050, 2048, 700)], 2048
    elif case == "one_link":
        ids, n_links = np.full(3000, 1500), 2048
    elif case == "ragged_links":
        ids, n_links = rng.integers(0, 2500, 5000), 2500
    elif case == "zero_padding_on_last_link":   # as the engine pins a plan
        ids, n_links = np.r_[rng.integers(0, 3000, 2300),
                             np.full(772, 2999)], 3000
    elif case == "single_pair_block":
        ids, n_links = rng.integers(0, 1500, 700), 1500
    ids = np.sort(ids)
    vals = rng.random(ids.shape[0]).astype(np.float32) + 0.5
    if case == "zero_padding_on_last_link":
        vals[2300:] = 0.0
    return ids, vals, n_links


def _schedule_of(ids, n_links):
    from repro.kernels.segment_sum.segment_sum import sorted_schedule
    off = np.zeros(n_links + 1, dtype=np.int64)
    np.cumsum(np.bincount(ids, minlength=n_links), out=off[1:])
    return sorted_schedule(off)


@pytest.mark.parametrize("case", [
    "empty_segment_blocks", "link_crosses_pair_block",
    "pair_block_straddles_segment_blocks", "one_link", "ragged_links",
    "zero_padding_on_last_link", "single_pair_block"])
def test_sorted_segment_sum_matches_ref(case):
    from repro.kernels.segment_sum import segment_sum_ref
    from repro.kernels.segment_sum.segment_sum import (
        segment_sum_sorted_pallas, sorted_grid_steps)

    ids, vals, n_links = _sorted_pairs(case, np.random.default_rng(7))
    sched = _schedule_of(ids, n_links)
    assert sched.shape == (3 * sorted_grid_steps(ids.shape[0], n_links),)
    out = segment_sum_sorted_pallas(jnp.asarray(vals), jnp.asarray(ids),
                                    jnp.asarray(sched), n_links,
                                    interpret=True)
    ref = segment_sum_ref(jnp.asarray(vals), jnp.asarray(ids), n_links)
    assert out.shape == (n_links,)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_sorted_segment_sum_reads_no_pair_past_its_schedule():
    """The engine hands over the whole pair list: the unsorted tail
    past the sorted head is left to the dense kernel."""
    from repro.kernels.segment_sum import segment_sum_ref
    from repro.kernels.segment_sum.segment_sum import (
        segment_sum_sorted_pallas)

    rng = np.random.default_rng(8)
    head = np.sort(rng.integers(0, 3000, 2048))
    tail = rng.integers(0, 3000, 1024)
    vals = rng.random(3072).astype(np.float32)
    out = segment_sum_sorted_pallas(
        jnp.asarray(vals), jnp.asarray(np.r_[head, tail]),
        jnp.asarray(_schedule_of(head, 3000)), 3000, interpret=True)
    ref = segment_sum_ref(jnp.asarray(vals[:2048]), jnp.asarray(head), 3000)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_sorted_segment_sum_vmaps_lanes_with_their_own_schedules():
    """The lockstep batch: lanes hold different plans, so different
    schedules of one length."""
    import jax

    from repro.kernels.segment_sum import segment_sum_ref
    from repro.kernels.segment_sum.segment_sum import (
        segment_sum_sorted_pallas)

    rng = np.random.default_rng(9)
    ids = np.stack([np.sort(rng.integers(0, 3000, 4096)),
                    np.sort(rng.integers(2000, 2100, 4096))])
    vals = rng.random((2, 4096)).astype(np.float32)
    scheds = np.stack([_schedule_of(i, 3000) for i in ids])
    assert not np.array_equal(scheds[0], scheds[1])
    out = jax.vmap(lambda v, i, s: segment_sum_sorted_pallas(
        v, i, s, 3000, interpret=True))(jnp.asarray(vals),
                                        jnp.asarray(ids),
                                        jnp.asarray(scheds))
    ref = jax.vmap(lambda v, i: segment_sum_ref(v, i, 3000))(
        jnp.asarray(vals), jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# path_gather: a per-link vector read at every hop of every candidate
# path, the link table held in VMEM.
# --------------------------------------------------------------------------
def _index_view(ids):
    """Flat ``ids`` zero-padded to `index_rows` rows of 128."""
    from repro.kernels.path_gather import index_rows

    rows = index_rows(ids.size)
    return jnp.asarray(np.pad(ids, (0, rows * 128 - ids.size)).reshape(
        rows, 128))


@pytest.mark.parametrize("n_links,n_ids", [
    (56_448, 4_096),             # Aries' link ids
    (66_048, 4_096),             # the balanced Dragonfly's link ids
    (300, 4_096),                # a ragged table
    (128, 4_096),                # one row of 128
    (56_448, 120_016 * 48),      # 120k-flow phase: rows x candidates x hops
    (66_048, 65_296 * 48),       # the 256-rank all-to-all's phase
    (1_400, 1_024 * 128),        # a tail-sized 1,024 rows of ids
], ids=["aries", "dfly", "ragged", "one_row", "m2m_ids", "a2a_ids",
        "rows_1024"])
def test_path_gather_is_the_table_at_the_ids(n_links, n_ids):
    from repro.kernels.path_gather import path_gather_pallas

    table = RNG.standard_normal(n_links).astype(np.float32)
    ids = RNG.integers(0, n_links, n_ids).astype(np.int32)
    ids[:2] = 0, n_links - 1
    view = _index_view(ids)
    out = np.asarray(path_gather_pallas(jnp.asarray(table), view,
                                        interpret=True))
    assert out.shape == view.shape
    np.testing.assert_array_equal(out.reshape(-1)[:n_ids], table[ids])


def test_path_gather_pads_rows_off_its_block():
    """[m, 128] ids whose m is a multiple of neither block come back as
    [m, 128]."""
    from repro.kernels.path_gather import path_gather_pallas
    from repro.kernels.path_gather.path_gather import BLOCK_ROWS, SUB_ROWS

    m = BLOCK_ROWS + SUB_ROWS + 5
    table = RNG.standard_normal(3_000).astype(np.float32)
    ids = RNG.integers(0, 3_000, (m, 128)).astype(np.int32)
    out = path_gather_pallas(jnp.asarray(table), jnp.asarray(ids),
                             interpret=True)
    np.testing.assert_array_equal(np.asarray(out), table[ids])


def test_path_gather_vmaps_lane_by_lane():
    """The lockstep batch: each lane gathers from its own table through
    its own ids, or through ids all lanes share."""
    import jax

    from repro.kernels.path_gather import path_gather_pallas

    tables = RNG.standard_normal((3, 1_000)).astype(np.float32)
    ids = RNG.integers(0, 1_000, (3, 700)).astype(np.int32)
    views = jnp.stack([_index_view(i) for i in ids])

    def kern(t, v):
        return path_gather_pallas(t, v, interpret=True)

    out = np.asarray(jax.vmap(kern)(jnp.asarray(tables), views))
    for lane in range(3):
        np.testing.assert_array_equal(out[lane].reshape(-1)[:700],
                                      tables[lane][ids[lane]])
    shared = np.asarray(jax.vmap(kern, in_axes=(0, None))(
        jnp.asarray(tables), views[0]))
    for lane in range(3):
        np.testing.assert_array_equal(shared[lane].reshape(-1)[:700],
                                      tables[lane][ids[0]])
