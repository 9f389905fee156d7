"""PR-3 fast-path guarantees: golden traces vs the pre-refactor oracle,
PhasePlan reuse, the jax backend tolerance matrix, the background-flow
disjointness regression, and the notification-channel OFF-switch
differential (threshold=inf replays the channel-free simulator
bit-for-bit across the whole topology family)."""

import hashlib
import sys

import numpy as np
import pytest

from repro.core.strategies import RoutingMode
from repro.dragonfly import (DragonflySimulator, DragonflyTopology,
                             SimParams, TenantSegments, TopologyParams)
from repro.dragonfly.reference import reference_run_phase
from repro.dragonfly.routing import RoutingPolicy, spray_weights
from repro.dragonfly.topology import (make_allocation,
                                      registered_topologies,
                                      small_topology)
from repro.faults import FaultSchedule, link_down, router_down

TOPO = DragonflyTopology(TopologyParams(n_groups=4, chassis_per_group=2,
                                        blades_per_chassis=4))
N = 600


def _flows(seed=42, n=N):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, TOPO.params.n_nodes, size=n)
    dst = (src + rng.integers(1, TOPO.params.n_nodes, size=n)) \
        % TOPO.params.n_nodes
    size = rng.pareto(1.2, size=n) * 65536 + 1024
    return src, dst, size


def _assert_flowresult_equal(a, b, rtol=0.0):
    if rtol == 0.0:
        assert np.array_equal(a.t_us, b.t_us)
        assert np.array_equal(a.latency_us, b.latency_us)
        assert np.array_equal(a.stalls_per_flit, b.stalls_per_flit)
        assert a.nonmin_fraction == b.nonmin_fraction
    else:
        np.testing.assert_allclose(a.t_us, b.t_us, rtol=rtol)
        np.testing.assert_allclose(a.latency_us, b.latency_us, rtol=rtol)
        np.testing.assert_allclose(a.stalls_per_flit, b.stalls_per_flit,
                                   rtol=rtol, atol=1e-6)
        assert a.nonmin_fraction == pytest.approx(b.nonmin_fraction,
                                                  rel=max(rtol, 1e-6),
                                                  abs=1e-6)
    assert np.array_equal(a.flits, b.flits)
    assert np.array_equal(a.packets, b.packets)


# --------------------------------------------------------------------------
# Golden traces: the numpy fast path replays the pre-refactor simulator
# seed-for-seed, BIT-identical — including congested phases, where the
# hoisted score base re-gathers the hot rows with the combined estimate.
# --------------------------------------------------------------------------
@pytest.mark.parametrize("mode", list(RoutingMode))
def test_numpy_fast_path_bit_identical_to_reference(mode):
    src, dst, size = _flows()
    al = make_allocation(TOPO, 8, spread="inter_groups", seed=3)
    sp = SimParams(seed=0)
    ref_sim = DragonflySimulator(TOPO, sp)
    fast_sim = DragonflySimulator(TOPO, sp)
    pol = RoutingPolicy(mode)
    for _ in range(3):
        ra = reference_run_phase(ref_sim, src, dst, size, pol, al)
        rb = fast_sim.run_phase(src, dst, size, pol, al)
        _assert_flowresult_equal(ra, rb)
        assert np.array_equal(ref_sim.link_queue_s, fast_sim.link_queue_s)
        assert np.array_equal(ref_sim.est_memory_s, fast_sim.est_memory_s)
    assert ref_sim.clock_s == fast_sim.clock_s
    ca = ref_sim.counters[al.allocation_id]
    cb = fast_sim.counters[al.allocation_id]
    assert ca.request_flits == cb.request_flits
    assert ca.request_packets_cumulative_latency_us \
        == cb.request_packets_cumulative_latency_us


@pytest.mark.parametrize("kw", [
    dict(route_feedback_iters=1),
    dict(bg_enable=False),
    dict(bg_bytes_scale=5e8, bg_flows_per_phase=32),   # congested links
    dict(min_phase_window_s=5e-6),
    dict(max_flows=200),                               # subsample path
])
def test_numpy_fast_path_bit_identical_configs(kw):
    src, dst, size = _flows(seed=7)
    al = make_allocation(TOPO, 8, spread="inter_groups", seed=1)
    sp = SimParams(seed=11, **kw)
    ref_sim = DragonflySimulator(TOPO, sp)
    fast_sim = DragonflySimulator(TOPO, sp)
    pol = RoutingPolicy(RoutingMode.ADAPTIVE_3)
    for _ in range(3):
        ra = reference_run_phase(ref_sim, src, dst, size, pol, al)
        rb = fast_sim.run_phase(src, dst, size, pol, al)
        _assert_flowresult_equal(ra, rb)
        assert np.array_equal(ref_sim.link_queue_s, fast_sim.link_queue_s)


def test_numpy_fast_path_bit_identical_mixed_modes():
    """Per-flow modes (the PolicyEngine path) through the int mode-code
    bias table match the reference's per-unique-mode masked passes."""
    src, dst, size = _flows(seed=5)
    pool = [RoutingMode.ADAPTIVE_0, RoutingMode.ADAPTIVE_1,
            RoutingMode.ADAPTIVE_3, RoutingMode.MIN_HASH,
            RoutingMode.NMIN_HASH]
    modes = np.empty(N, dtype=object)
    modes[:] = [pool[i % len(pool)] for i in range(N)]
    al = make_allocation(TOPO, 8, spread="inter_groups", seed=2)
    sp = SimParams(seed=4)
    ref_sim = DragonflySimulator(TOPO, sp)
    fast_sim = DragonflySimulator(TOPO, sp)
    pol = RoutingPolicy(RoutingMode.ADAPTIVE_0)
    ra = reference_run_phase(ref_sim, src, dst, size, pol, al, modes=modes)
    rb = fast_sim.run_phase(src, dst, size, pol, al, modes=modes)
    _assert_flowresult_equal(ra, rb)
    assert np.array_equal(ref_sim.link_queue_s, fast_sim.link_queue_s)


def test_empty_app_phase_bit_identical():
    """Background-only phases (table1's idle probe) stay equivalent."""
    sp = SimParams(seed=9)
    ref_sim = DragonflySimulator(TOPO, sp)
    fast_sim = DragonflySimulator(TOPO, sp)
    pol = RoutingPolicy(RoutingMode.ADAPTIVE_0)
    e = np.zeros(0, dtype=np.int64)
    for _ in range(2):
        reference_run_phase(ref_sim, e, e, np.zeros(0), pol)
        fast_sim.run_phase(e, e, np.zeros(0), pol)
    assert np.array_equal(ref_sim.link_queue_s, fast_sim.link_queue_s)
    assert ref_sim.total_flits_all_jobs == fast_sim.total_flits_all_jobs


# --------------------------------------------------------------------------
# PhasePlan reuse.
# --------------------------------------------------------------------------
def test_phase_plan_reuse_deterministic_and_cached():
    src, dst, size = _flows(seed=1)
    al = make_allocation(TOPO, 8, spread="inter_groups", seed=1)
    pol = RoutingPolicy(RoutingMode.ADAPTIVE_0)
    runs = []
    for _ in range(2):
        sim = DragonflySimulator(TOPO, SimParams(seed=3))
        plan = sim.plan_for(src, dst, size)
        assert sim.plan_for(src, dst, size) is plan   # content-addressed
        rs = [sim.run_phase(None, None, None, pol, al, plan=plan)
              for _ in range(3)]
        runs.append(rs)
    for ra, rb in zip(*runs):                         # seeded-deterministic
        _assert_flowresult_equal(ra, rb)


def test_phase_plan_matches_planless_statistics():
    """A plan-reused run is a different RNG trajectory but the same
    physics: per-flow times stay within a loose statistical band."""
    src, dst, size = _flows(seed=8)
    al = make_allocation(TOPO, 8, spread="inter_groups", seed=4)
    pol = RoutingPolicy(RoutingMode.ADAPTIVE_0)
    sim_a = DragonflySimulator(TOPO, SimParams(seed=5))
    sim_b = DragonflySimulator(TOPO, SimParams(seed=5))
    ra = sim_a.run_phase(src, dst, size, pol, al)
    rb = sim_b.run_phase(None, None, None, pol, al,
                         plan=sim_b.plan_for(src, dst, size))
    assert rb.t_us.shape == ra.t_us.shape
    assert np.median(rb.t_us) == pytest.approx(np.median(ra.t_us), rel=0.2)


def test_phase_plan_subsample_keeps_modes_aligned():
    src, dst, size = _flows(seed=2, n=500)
    sim = DragonflySimulator(TOPO, SimParams(seed=1, max_flows=200))
    plan = sim.make_plan(src, dst, size)
    assert plan.n_flows == 200 and plan.n_flows_in == 500
    modes = np.empty(500, dtype=object)
    modes[:] = [RoutingMode.ADAPTIVE_0] * 500
    pol = RoutingPolicy(RoutingMode.ADAPTIVE_0)
    res = sim.run_phase(None, None, None, pol, modes=modes, plan=plan)
    assert res.t_us.shape == (200,)
    with pytest.raises(ValueError):
        sim.run_phase(None, None, None, pol, modes=modes[:10], plan=plan)


# --------------------------------------------------------------------------
# Satellite regression: background flows never touch the allocation.
# --------------------------------------------------------------------------
def test_bg_flows_disjoint_from_allocation():
    """Pre-fix, 3 resample retries could silently leave other-job flows
    on the allocation's nodes.  Cover a brutal case: the allocation owns
    almost the whole machine, so nearly every draw collides."""
    tp = TOPO.params
    keep_out = 5
    nodes = tuple(range(tp.n_nodes - keep_out))       # own all but 5 nodes
    al = make_allocation(TOPO, 4, spread="inter_nodes", seed=0)
    al = type(al)(allocation_id="huge", nodes=nodes)
    sim = DragonflySimulator(TOPO, SimParams(seed=0, bg_flows_per_phase=64))
    for _ in range(20):
        bg = sim._bg_flows(al)
        assert bg is not None
        src, dst, _ = bg
        assert not np.isin(src, nodes).any()
        assert not np.isin(dst, nodes).any()
        assert (src != dst).all()


def test_bg_flows_unchanged_when_disjoint():
    """When no draw collides, the fixed resampler consumes the RNG
    stream exactly like the seed implementation (golden determinism)."""
    sim_a = DragonflySimulator(TOPO, SimParams(seed=6))
    sim_b = DragonflySimulator(TOPO, SimParams(seed=6))
    bg_a = sim_a._bg_flows(None)
    bg_b = sim_b._bg_flows(None)
    for x, y in zip(bg_a, bg_b):
        assert np.array_equal(x, y)


# --------------------------------------------------------------------------
# jax backend: tolerance matrix; no fallback onto numpy.
# --------------------------------------------------------------------------
JAX_RTOL = 2e-2   # float32 pipeline vs float64 numpy (docs/performance.md)


@pytest.mark.parametrize("mode", list(RoutingMode))
def test_jax_backend_matches_numpy_within_tolerance(mode):
    src, dst, size = _flows(seed=3, n=250)
    al = make_allocation(TOPO, 8, spread="inter_groups", seed=1)
    sim_n = DragonflySimulator(TOPO, SimParams(seed=2))
    sim_j = DragonflySimulator(TOPO, SimParams(seed=2, backend="jax"))
    pol = RoutingPolicy(mode)
    rn = sim_n.run_phase(src, dst, size, pol, al)
    rj = sim_j.run_phase(src, dst, size, pol, al)
    np.testing.assert_allclose(rj.t_us, rn.t_us, rtol=JAX_RTOL)
    np.testing.assert_allclose(rj.latency_us, rn.latency_us, rtol=JAX_RTOL)
    np.testing.assert_allclose(rj.stalls_per_flit, rn.stalls_per_flit,
                               rtol=JAX_RTOL, atol=1e-4)
    assert rj.nonmin_fraction == pytest.approx(rn.nonmin_fraction,
                                               rel=JAX_RTOL, abs=1e-4)


def test_jax_backend_matches_numpy_mixed_modes():
    src, dst, size = _flows(seed=3, n=250)
    pool = [RoutingMode.ADAPTIVE_0, RoutingMode.ADAPTIVE_2,
            RoutingMode.ADAPTIVE_3, RoutingMode.IN_ORDER]
    modes = np.empty(250, dtype=object)
    modes[:] = [pool[i % len(pool)] for i in range(250)]
    sim_n = DragonflySimulator(TOPO, SimParams(seed=2))
    sim_j = DragonflySimulator(TOPO, SimParams(seed=2, backend="jax"))
    pol = RoutingPolicy(RoutingMode.ADAPTIVE_0)
    rn = sim_n.run_phase(src, dst, size, pol, modes=modes)
    rj = sim_j.run_phase(src, dst, size, pol, modes=modes)
    np.testing.assert_allclose(rj.t_us, rn.t_us, rtol=JAX_RTOL)


@pytest.mark.parametrize("missing", ["jax", "jax.experimental.pallas"])
def test_jax_backend_raises_when_jax_unusable(monkeypatch, missing):
    """With jax or Pallas unimportable, backend='jax' refuses to build
    the simulator: it never continues on numpy."""
    import jax.experimental

    for mod in ("repro.dragonfly.jax_backend",
                "repro.kernels.segment_sum.segment_sum"):
        monkeypatch.delitem(sys.modules, mod)
    monkeypatch.delattr(jax.experimental, "pallas", raising=False)
    monkeypatch.setitem(sys.modules, missing, None)
    with pytest.raises(RuntimeError, match="needs jax with Pallas"):
        DragonflySimulator(TOPO, SimParams(backend="jax"))
    DragonflySimulator(TOPO, SimParams())   # numpy needs neither


def test_unknown_backend_rejected():
    with pytest.raises(ValueError):
        DragonflySimulator(TOPO, SimParams(backend="cuda"))


# --------------------------------------------------------------------------
# Notification-channel OFF switch: notify_threshold_s=inf (the default)
# must be indistinguishable from a simulator without the channel — same
# RNG stream, same float ops, bit-identical results — no matter how the
# other notify knobs are set, on every registered topology, with mixed
# per-flow modes, tenants, and an active fault schedule.
# --------------------------------------------------------------------------
#: aggressively non-default channel knobs that must all be inert at inf
_NOTIFY_OFF = dict(notify_threshold_s=float("inf"), notify_clear_frac=0.9,
                   notify_delay_phases=0, notify_penalty_s=1.0)


def _digest(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()) \
        .hexdigest()[:16]


def _trace(sim, src, dst, size, pol, alloc=None, tenants=None,
           modes=None, phases=3):
    out = []
    for _ in range(phases):
        res = sim.run_phase(src, dst, size, pol, alloc, tenants=tenants,
                            modes=modes)
        assert res.notified is None          # disabled = no signal at all
        out.append((_digest(res.t_us), _digest(res.latency_us),
                    _digest(res.stalls_per_flit),
                    _digest(sim.link_queue_s),
                    _digest(sim.est_memory_s)))
    return out


def _family_flows(topo, seed=3, n=64):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, topo.n_nodes, size=n)
    dst = (src + rng.integers(1, topo.n_nodes, size=n)) % topo.n_nodes
    size = rng.pareto(1.2, size=n) * 65536 + 1024
    return src, dst, size


@pytest.mark.parametrize("name", registered_topologies())
@pytest.mark.parametrize("mode", [RoutingMode.ADAPTIVE_0,
                                  RoutingMode.ADAPTIVE_3])
def test_notify_off_bit_identical_topology_family(name, mode):
    topo = small_topology(name)
    src, dst, size = _family_flows(topo)
    pol = RoutingPolicy(mode)
    base = DragonflySimulator(topo, SimParams(seed=13))
    off = DragonflySimulator(topo, SimParams(seed=13, **_NOTIFY_OFF))
    assert not off.params.notify_enabled
    assert _trace(base, src, dst, size, pol) \
        == _trace(off, src, dst, size, pol)
    assert base.clock_s == off.clock_s
    assert off.notify_epoch() == 0


def test_notify_off_bit_identical_mixed_modes_and_allocation():
    src, dst, size = _flows(seed=17)
    pool = [RoutingMode.ADAPTIVE_0, RoutingMode.ADAPTIVE_1,
            RoutingMode.ADAPTIVE_3, RoutingMode.MIN_HASH]
    modes = np.empty(N, dtype=object)
    modes[:] = [pool[i % len(pool)] for i in range(N)]
    al = make_allocation(TOPO, 8, spread="inter_groups", seed=5)
    pol = RoutingPolicy(RoutingMode.ADAPTIVE_0)
    base = DragonflySimulator(TOPO, SimParams(seed=19))
    off = DragonflySimulator(TOPO, SimParams(seed=19, **_NOTIFY_OFF))
    assert _trace(base, src, dst, size, pol, alloc=al, modes=modes) \
        == _trace(off, src, dst, size, pol, alloc=al, modes=modes)
    ca, cb = base.counters[al.allocation_id], off.counters[al.allocation_id]
    assert ca.request_flits == cb.request_flits
    assert ca.congestion_notifications == cb.congestion_notifications == 0


def test_notify_off_bit_identical_tenants():
    src, dst, size = _flows(seed=23, n=200)
    al1 = make_allocation(TOPO, 8, spread="contiguous", seed=2,
                          allocation_id="a")
    al2 = make_allocation(TOPO, 8, spread="contiguous", seed=9,
                          allocation_id="b")
    seg = TenantSegments.of([al1, al2], [100, 100])
    pol = RoutingPolicy(RoutingMode.ADAPTIVE_0)
    base = DragonflySimulator(TOPO, SimParams(seed=29))
    off = DragonflySimulator(TOPO, SimParams(seed=29, **_NOTIFY_OFF))
    assert _trace(base, src, dst, size, pol, tenants=seg) \
        == _trace(off, src, dst, size, pol, tenants=seg)
    for aid in ("a", "b"):
        assert base.counters[aid].request_packets \
            == off.counters[aid].request_packets
        assert off.counters[aid].congestion_notifications == 0


@pytest.mark.parametrize("name", registered_topologies())
def test_notify_off_bit_identical_under_faults(name):
    topo = small_topology(name)
    src, dst, size = _family_flows(topo, seed=7)
    sched = FaultSchedule.of(
        link_down(start=1, end=3, n_random=2, link_kind="global", seed=4),
        router_down(start=2, end=3, n_random=1, seed=6))
    pol = RoutingPolicy(RoutingMode.ADAPTIVE_0)
    base = DragonflySimulator(topo, SimParams(seed=31, bg_enable=False),
                              faults=sched)
    off = DragonflySimulator(
        topo, SimParams(seed=31, bg_enable=False, **_NOTIFY_OFF),
        faults=sched)
    assert _trace(base, src, dst, size, pol, phases=4) \
        == _trace(off, src, dst, size, pol, phases=4)
    assert base.fault_epoch() == off.fault_epoch()
    assert off.notify_epoch() == 0


# --------------------------------------------------------------------------
# spray_weights micro-contract (satellite): rng=None path.
# --------------------------------------------------------------------------
def test_spray_weights_noiseless_path():
    pol = RoutingPolicy(RoutingMode.ADAPTIVE_0)
    scores = np.array([[1e-5, 2e-5, np.inf, np.nan],
                       [np.inf, np.inf, np.inf, np.inf]])
    w = spray_weights(scores, pol)
    assert np.isfinite(w).all()
    np.testing.assert_allclose(w.sum(1), [1.0, 0.0], atol=1e-12)
    assert w[0, 2] == w[0, 3] == 0.0      # inf/nan candidates get nothing
    # the input is never mutated (the old copy() is gone)
    assert np.isnan(scores[0, 3])
