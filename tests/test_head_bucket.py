"""The sorted head's size-relative bucket: one compiled shape per job size.

A plan's link-sorted head is padded to `jax_backend._head_len`: from
32,768 pairs on, a multiple of a bucket that grows with the head (an
eighth of its power of two), below that a multiple of the kernel's
1024-pair block.  Placements of one job draw pair counts a few percent
apart; in one bucket they share one `batch_signature` and one compiled
pipeline.
"""

import jax
import numpy as np
import pytest

from repro.core.strategies import RoutingMode
from repro.dragonfly import (DragonflySimulator, DragonflyTopology,
                             SimParams, TopologyParams, jax_backend)
from repro.dragonfly.routing import RoutingPolicy

#: the benchmark's cells: (least, most real head pairs of the cell's
#: band, the padded head every seed gets)
CELL_BANDS = {
    "aries12.halo3d512_protocol": (14_337, 15_360, 15_360),
    "aries12.m2m120k_plan": (3_759_105, 3_760_128, 3_932_160),
    "dfly_p8a16h8.m2m120k_plan": (2_524_161, 2_525_184, 2_621_440),
    "dfly_p8a16h8.a2a256_protocol": (1_179_649, 1_310_720, 1_310_720),
}


@pytest.mark.parametrize("cell", sorted(CELL_BANDS))
def test_each_cell_band_is_one_bucket(cell):
    lo, hi, padded = CELL_BANDS[cell]
    assert {jax_backend._head_len(p) for p in (lo, (lo + hi) // 2, hi)} \
        == {padded}


@pytest.mark.parametrize("p", [1, 1023, 1024, 1025, 14_337, 20_000,
                               32_767])
def test_heads_under_32768_pairs_keep_the_block_multiple(p):
    assert jax_backend._head_len(p) == -(-p // 1024) * 1024


def test_the_pad_stays_under_an_eighth_and_grows_with_the_head():
    p = np.unique(np.geomspace(1, 8e6, 6_000).astype(int))
    padded = np.array([jax_backend._head_len(int(x)) for x in p])
    assert (padded >= p).all() and (np.diff(padded) >= 0).all()
    big = p >= 32_768
    assert ((padded - p)[big] < p[big] / 8).all()
    assert (padded % 1024 == 0).all()


#: 1,400 links: two 1024-wide link blocks, the second ragged
TWO_BLOCKS = DragonflyTopology(TopologyParams(
    n_groups=5, chassis_per_group=2, blades_per_chassis=6))
POL = RoutingPolicy(RoutingMode.ADAPTIVE_0)
N_FLOWS = 1_500


def _flows(seed):
    rng = np.random.default_rng(seed)
    n = TWO_BLOCKS.n_nodes
    src = rng.integers(0, n, size=N_FLOWS)
    dst = (src + rng.integers(1, n, size=N_FLOWS)) % n
    return src, dst, rng.pareto(1.2, size=N_FLOWS) * 65_536 + 1_024


def _two_plans(sim):
    """Two plans of N_FLOWS rows whose real head pairs lie in different
    1024-pair blocks of one size-relative bucket."""
    plans = {}
    for seed in range(40):
        src, dst, size = _flows(seed)
        plan = sim.plan_for(src, dst, size)
        p = int(plan.pair_links.shape[0])
        for q, other in plans.items():
            if p // 1024 != q // 1024 \
                    and jax_backend._head_len(p) == jax_backend._head_len(q):
                return other, (src, dst, size, plan)
        plans[p] = (src, dst, size, plan)
    raise AssertionError("no two plans in one bucket")


class _Compiles:
    def __enter__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def _signature_of_two_phases(sim, src, dst, size, plan):
    """A plan's first phase (its head pinned) and a steady one; the
    first phase's `batch_signature`."""
    ctx = sim._phase_begin(src, dst, size, POL, plan=plan)
    sig = jax_backend.batch_signature(sim, ctx)
    sim._phase_finish(ctx, sim._run_kernel(ctx))
    sim.run_phase(src, dst, size, POL, plan=plan)
    return sig


def test_two_placements_in_one_bucket_compile_once():
    sim = DragonflySimulator(TWO_BLOCKS, SimParams(seed=3, backend="jax"))
    a, b = _two_plans(sim)
    assert a[3].pair_links.shape[0] > 32_768     # past the 1024 multiples
    sig_a = _signature_of_two_phases(sim, *a)
    with _Compiles() as compiles:
        sig_b = _signature_of_two_phases(sim, *b)
    assert sig_a == sig_b
    assert a[3].device_bundle["p_sorted"] == b[3].device_bundle["p_sorted"]
    assert compiles.n == 0
