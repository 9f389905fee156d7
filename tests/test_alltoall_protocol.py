"""The paper's Algorithm 1 driving an all-to-all job on a balanced
Dragonfly, through `run_iteration_engine` with plans reused.

Algorithm 1 replaces its default mode by ``mode_a_alltoall``
(ADAPTIVE_1) at an all-to-all call site (paper §4.2).  The jax engine
replays the numpy engine's plan-reused phases within the pinned
tolerance, and the numpy engine's planless phases are the pre-refactor
oracle's (`dragonfly/reference.py`).
"""

import numpy as np
import pytest

from repro.core.strategies import RoutingMode
from repro.dragonfly import DragonflySimulator, SimParams, make_topology
from repro.dragonfly.reference import reference_run_phase
from repro.dragonfly.topology import Allocation
from repro.dragonfly.traffic import (PATTERN_KIND, alltoall, engine_for_arm,
                                     run_iteration_engine)
from repro.policy import AppAwareConfig

JAX_RTOL = 2e-2   # float32 pipeline vs float64 numpy (docs/performance.md)
ORACLE_RTOL = 1e-9  # the hoisted score base reassociates one float64 sum

TOPO = make_topology("dragonfly:p=2,a=4,h=2,g=9")
N_RANKS = 16
ITERATIONS = 3


def _alloc():
    rng = np.random.default_rng(11)
    nodes = rng.choice(TOPO.n_nodes, size=N_RANKS, replace=False)
    return Allocation("a2a", tuple(int(n) for n in nodes))


def _run(params=SimParams(seed=7), use_plans=True, oracle=False):
    """ITERATIONS all-to-alls under app_aware: per phase, the modes the
    engine chose and the result."""
    sim = DragonflySimulator(TOPO, params)
    seen = []
    run = sim.run_phase

    def record(src, dst, size, policy, allocation=None, modes=None,
               plan=None, tenants=None):
        if oracle:
            res = reference_run_phase(sim, src, dst, size, policy,
                                      allocation, modes=modes)
        else:
            res = run(src, dst, size, policy, allocation, modes=modes,
                      plan=plan, tenants=tenants)
        seen.append((set(modes), res))
        return res

    sim.run_phase = record
    engine = engine_for_arm("app_aware", sim, AppAwareConfig(), seed=3)
    phases = alltoall(N_RANKS, 131_072)
    for _ in range(ITERATIONS):
        run_iteration_engine(sim, _alloc(), phases, engine, site="alltoall",
                             kind=PATTERN_KIND["alltoall"],
                             use_plans=use_plans)
    return seen


def _close(a, b, rtol, atol):
    np.testing.assert_allclose(a.t_us, b.t_us, rtol=rtol)
    np.testing.assert_allclose(a.latency_us, b.latency_us, rtol=rtol)
    np.testing.assert_allclose(a.stalls_per_flit, b.stalls_per_flit,
                               rtol=rtol, atol=atol)


def test_algorithm1_takes_its_alltoall_branch():
    seen = _run()
    assert len(seen) == ITERATIONS and len(seen[0][1].t_us) == 16 * 15
    assert seen[0][0] == {RoutingMode.ADAPTIVE_1}
    assert all(len(m) == 1 for m, _ in seen)     # one mode a phase
    assert RoutingMode.ADAPTIVE_0 not in set().union(*(m for m, _ in seen))


@pytest.mark.parametrize("kernel", ["off", "on"])
def test_jax_replays_numpy_with_plans_reused(kernel):
    ref = _run()
    got = _run(SimParams(seed=7, backend="jax", pallas_kernel=kernel))
    assert [m for m, _ in got] == [m for m, _ in ref]
    for (_, g), (_, r) in zip(got, ref):
        _close(g, r, JAX_RTOL, 1e-4)


def test_numpy_planless_phases_are_the_oracles():
    got = _run(use_plans=False)
    ref = _run(use_plans=False, oracle=True)
    assert [m for m, _ in got] == [m for m, _ in ref]
    for (_, g), (_, r) in zip(got, ref):
        _close(g, r, ORACLE_RTOL, 1e-12)
