"""The Pallas segment sums and path gather compile for a TPU v5e at the
engine's widths.

Interpret-mode tests (tests/test_kernels.py) cannot see what Mosaic
refuses: a 1-D block whose tiling differs from XLA's T(1024) layout of
the operand, or a batched 1-D block.  These tests compile the kernels,
not interpreted, for one chip of a described (not attached) ``v5e:2x2``
at the real widths of the jax phase engine on the default Aries machine
(56,448 links) and on the balanced Dragonfly (66,048).  The topology is
described inside a fixture, never at import time, so only the worker
that runs this file loads the TPU compiler; where it cannot be
described the tests skip.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.dragonfly.jax_backend import _PAIR_BUCKET_FULL, _PAIR_BUCKET_PLAN
from repro.kernels.segment_sum.segment_sum import (
    segment_sum_pallas, segment_sum_sorted_pallas, sorted_grid_steps)

ARIES_LINKS = 56_448
DFLY_LINKS = 66_048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip executable cannot be read back without the chip:
    # keep it out of any persistent cache the environment turned on
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("n_pairs,n_segs", [
    (3_760_896, ARIES_LINKS),          # plan-pinned app pairs, 120k flows
    (120_016, ARIES_LINKS),            # NIC rows: 120k app + 16 bg flows
    (_PAIR_BUCKET_PLAN, ARIES_LINKS),  # per-phase background bucket
    (_PAIR_BUCKET_FULL, ARIES_LINKS),  # planless pair bucket
    (5, 300),                          # small ragged case
], ids=["app_pairs", "nic_rows", "bg_bucket", "planless_bucket", "ragged"])
def test_segment_sum_compiles_for_v5e(one_chip, n_pairs, n_segs):
    vals = jax.ShapeDtypeStruct((n_pairs,), jnp.float32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((n_pairs,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda v, i: segment_sum_pallas(v, i, n_segs)).lower(
            vals, ids).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_vmapped_segment_sum_compiles_for_v5e(one_chip):
    """The lockstep batch vmaps the phase pipeline, kernel included."""
    lanes, n_pairs = 2, _PAIR_BUCKET_FULL
    vals = jax.ShapeDtypeStruct((lanes, n_pairs), jnp.float32,
                                sharding=one_chip)
    ids = jax.ShapeDtypeStruct((lanes, n_pairs), jnp.int32,
                               sharding=one_chip)
    compiled = jax.jit(jax.vmap(
        lambda v, i: segment_sum_pallas(v, i, ARIES_LINKS))).lower(
            vals, ids).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("lanes,n_head,n_links", [
    (None, 3_932_160, ARIES_LINKS),    # plan-pinned head, 120k flows
    (None, 15_360, ARIES_LINKS),       # the 512-rank protocol job's head
    (2, 15_360, ARIES_LINKS),          # the lockstep batch, lane by lane
    (None, 2_621_440, DFLY_LINKS),     # 120k flows, balanced Dragonfly
    (None, 1_310_720, DFLY_LINKS),     # the 256-rank all-to-all's head
], ids=["aries_head", "protocol_head", "vmapped", "dfly_head", "a2a_head"])
def test_sorted_segment_sum_compiles_for_v5e(one_chip, lanes, n_head,
                                             n_links):
    """The engine hands the sorted kernel the whole pair list (head,
    padded by `_head_len`, plus the background bucket) and the plan's
    scalar-prefetched schedule."""
    n_pairs = n_head + _PAIR_BUCKET_PLAN
    lead = () if lanes is None else (lanes,)
    vals = jax.ShapeDtypeStruct((*lead, n_pairs), jnp.float32,
                                sharding=one_chip)
    ids = jax.ShapeDtypeStruct((*lead, n_pairs), jnp.int32,
                               sharding=one_chip)
    sched = jax.ShapeDtypeStruct(
        (*lead, 3 * sorted_grid_steps(n_head, n_links)), jnp.int32,
        sharding=one_chip)

    def one(v, i, s):
        return segment_sum_sorted_pallas(v, i, s, n_links)

    fn = one if lanes is None else jax.vmap(one)
    compiled = jax.jit(fn).lower(vals, ids, sched).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("lanes,n_links,n_rows", [
    (None, ARIES_LINKS, 120_016),    # 120k-flow phase on Aries
    (None, DFLY_LINKS, 120_016),     # 120k flows, balanced Dragonfly
    (None, DFLY_LINKS, 65_296),      # the 256-rank all-to-all's phase
    (None, ARIES_LINKS, 464),        # the 512-rank protocol job's phase
    (2, ARIES_LINKS, 464),           # the lockstep batch, lane by lane
], ids=["aries_m2m", "dfly_m2m", "dfly_a2a", "protocol", "vmapped"])
def test_path_gather_compiles_for_v5e(one_chip, lanes, n_links, n_rows):
    """The engine hands the path gather a per-link table and the
    candidates' [rows, 6, 8] link ids as their `path_view`."""
    from repro.dragonfly.jax_backend import path_view
    from repro.kernels.path_gather import path_gather_pallas

    lead = () if lanes is None else (lanes,)
    view = jax.eval_shape(path_view, jax.ShapeDtypeStruct(
        (n_rows, 6, 8), jnp.int32)).shape
    table = jax.ShapeDtypeStruct((*lead, n_links), jnp.float32,
                                 sharding=one_chip)
    ids = jax.ShapeDtypeStruct((*lead, *view), jnp.int32, sharding=one_chip)
    fn = path_gather_pallas if lanes is None else jax.vmap(
        path_gather_pallas)
    compiled = jax.jit(fn).lower(table, ids).compile()
    assert "tpu_custom_call" in compiled.as_text()
