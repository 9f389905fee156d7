"""Device-resident jax phase-engine guarantees.

What the jitted pipeline must preserve (docs/performance.md):

  * numpy parity across the topology family AND across the hard phase
    kinds that used to force a numpy fallback — fault candidate masks
    and active congestion notifications — proven to have actually run
    on jax via the `PIPELINE_CALLS` dispatch counters;
  * device/queue state correctness across `reset_queues()` and
    fault/notify epoch bumps (the numpy backend is the oracle, and the
    plan cache must hand back a FRESH device bundle after a bump);
  * the `SimParams.pallas_kernel` knob: "on" (interpret off-TPU) agrees
    with "off" within the pinned tolerance, "auto" resolves to the ref
    path on CPU, junk is rejected;
  * `run_phase_batch` / the tenancy lockstep sweep: batching changes
    the dispatch, never the results.
"""

import numpy as np
import pytest

from repro.core.strategies import RoutingMode
from repro.dragonfly import (DragonflySimulator, DragonflyTopology,
                             SimParams, TopologyParams)
from repro.dragonfly.routing import RoutingPolicy
from repro.dragonfly.simulator import run_phase_batch
from repro.dragonfly.topology import make_allocation, small_topology
from repro.faults import FaultSchedule, link_down

JAX_RTOL = 2e-2   # float32 pipeline vs float64 numpy (docs/performance.md)

TOPO = DragonflyTopology(TopologyParams(n_groups=4, chassis_per_group=2,
                                        blades_per_chassis=4))


def _flows(topo, seed=42, n=400):
    rng = np.random.default_rng(seed)
    n_nodes = topo.n_nodes
    src = rng.integers(0, n_nodes, size=n)
    dst = (src + rng.integers(1, n_nodes, size=n)) % n_nodes
    size = rng.pareto(1.2, size=n) * 65536 + 1024
    return src, dst, size


def _assert_close(rj, rn, rtol=JAX_RTOL):
    np.testing.assert_allclose(rj.t_us, rn.t_us, rtol=rtol)
    np.testing.assert_allclose(rj.latency_us, rn.latency_us, rtol=rtol)
    np.testing.assert_allclose(rj.stalls_per_flit, rn.stalls_per_flit,
                               rtol=rtol, atol=1e-4)
    assert np.array_equal(rj.flits, rn.flits)


def _dispatches():
    from repro.dragonfly.jax_backend import PIPELINE_CALLS
    return sum(PIPELINE_CALLS.values())


# --------------------------------------------------------------------------
# Parity matrix: topology family x {healthy, faulted, notifying} — and
# the jax pipeline must actually DISPATCH on the masked/notified phases
# (they used to silently fall back to numpy).
# --------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["aries", "dragonfly", "dragonfly_plus"])
@pytest.mark.parametrize("scenario", ["healthy", "faulted", "notifying"])
def test_jax_parity_topology_family(name, scenario):
    topo = small_topology(name)
    src, dst, size = _flows(topo, seed=7)
    kw = {"seed": 5}
    if scenario == "notifying":
        kw.update(notify_threshold_s=1e-5, notify_penalty_s=300e-6)
    sims = {}
    for be in ("numpy", "jax"):
        sim = DragonflySimulator(topo, SimParams(backend=be, **kw))
        if scenario == "faulted":
            sim.set_faults(FaultSchedule.of(
                link_down([1, topo.n_links // 2], start=0)))
        sims[be] = sim
    pol = RoutingPolicy(RoutingMode.ADAPTIVE_2)
    before = _dispatches()
    for _ in range(3):      # phase 2+ sees raised notifications / queues
        rn = sims["numpy"].run_phase(src, dst, size, pol)
        rj = sims["jax"].run_phase(src, dst, size, pol)
        _assert_close(rj, rn)
    assert _dispatches() - before == 3
    if scenario == "notifying":
        assert sims["jax"].notify_epoch() == sims["numpy"].notify_epoch()


def test_jax_faulted_phase_runs_on_device_with_plan():
    """Fault cand_mask phases ride the plan-pinned device path too, and
    stranded flows (all candidates dead) agree with numpy."""
    src, dst, size = _flows(TOPO, seed=11)
    sims, plans = {}, {}
    for be in ("numpy", "jax"):
        sim = DragonflySimulator(TOPO, SimParams(seed=3, backend=be))
        sim.set_faults(FaultSchedule.of(link_down(n_random=6, seed=4)))
        sims[be] = sim
        plans[be] = sim.plan_for(src, dst, size)
    pol = RoutingPolicy(RoutingMode.ADAPTIVE_3)
    before = _dispatches()
    for _ in range(2):
        rn = sims["numpy"].run_phase(src, dst, size, pol,
                                     plan=plans["numpy"])
        rj = sims["jax"].run_phase(src, dst, size, pol, plan=plans["jax"])
        _assert_close(rj, rn)
    assert _dispatches() - before == 2


# --------------------------------------------------------------------------
# Device/queue state across reset_queues() and epoch bumps.
# --------------------------------------------------------------------------
def test_jax_state_survives_reset_and_epoch_bumps():
    """One interleaved life: phases -> reset_queues -> phases -> fault
    epoch bump -> phases.  The jax sim must track the numpy oracle
    through every transition, and the plan cache must hand back a fresh
    plan (fresh device bundle) after the bump."""
    src, dst, size = _flows(TOPO, seed=13)
    pol = RoutingPolicy(RoutingMode.ADAPTIVE_0)
    sim_n = DragonflySimulator(TOPO, SimParams(seed=9))
    sim_j = DragonflySimulator(TOPO, SimParams(seed=9, backend="jax"))

    plan_j = sim_j.plan_for(src, dst, size)
    plan_n = sim_n.plan_for(src, dst, size)
    for _ in range(2):
        _assert_close(sim_j.run_phase(src, dst, size, pol, plan=plan_j),
                      sim_n.run_phase(src, dst, size, pol, plan=plan_n))
    assert plan_j.device_bundle is not None

    sim_j.reset_queues()
    sim_n.reset_queues()
    assert np.all(sim_j.link_queue_s == 0.0)
    _assert_close(sim_j.run_phase(src, dst, size, pol, plan=plan_j),
                  sim_n.run_phase(src, dst, size, pol, plan=plan_n))

    # epoch bumps on an active-set CHANGE: activate links mid-run
    sim_j.set_faults(FaultSchedule.of(link_down([2, 5], start=4)))
    sim_n.set_faults(FaultSchedule.of(link_down([2, 5], start=4)))
    sim_j.run_phase(src, dst, size, pol)      # phase 3: still healthy
    sim_n.run_phase(src, dst, size, pol)
    assert sim_j.fault_epoch() == sim_n.fault_epoch() > 0
    plan_j2 = sim_j.plan_for(src, dst, size)
    plan_n2 = sim_n.plan_for(src, dst, size)
    assert plan_j2 is not plan_j              # epoch keyed the cache
    assert plan_j2.device_bundle is None      # fresh bundle, pinned lazily
    _assert_close(sim_j.run_phase(src, dst, size, pol, plan=plan_j2),
                  sim_n.run_phase(src, dst, size, pol, plan=plan_n2))
    assert plan_j2.device_bundle is not None


# --------------------------------------------------------------------------
# pallas_kernel knob.
# --------------------------------------------------------------------------
def test_pallas_kernel_on_agrees_with_off():
    """force-"on" (interpret mode off-TPU) replays the "off" scatter
    path within the pinned tolerance — the kernel parity contract."""
    src, dst, size = _flows(TOPO, seed=17, n=150)
    pol = RoutingPolicy(RoutingMode.ADAPTIVE_0)
    results = {}
    for knob in ("off", "on"):
        sim = DragonflySimulator(
            TOPO, SimParams(seed=4, backend="jax", pallas_kernel=knob))
        results[knob] = sim.run_phase(src, dst, size, pol)
    _assert_close(results["on"], results["off"], rtol=1e-4)


#: 1,400 links: two 1024-wide link blocks, the second ragged
TWO_BLOCKS = DragonflyTopology(TopologyParams(
    n_groups=5, chassis_per_group=2, blades_per_chassis=6))


def _reused_plan_phases(knob, n_phases=2, backend="jax"):
    """Plan-reused phases (sorted head) with the background tail."""
    src, dst, size = _flows(TWO_BLOCKS, seed=19, n=300)
    sim = DragonflySimulator(
        TWO_BLOCKS, SimParams(seed=5, backend=backend, pallas_kernel=knob))
    plan = sim.plan_for(src, dst, size)
    pol = RoutingPolicy(RoutingMode.ADAPTIVE_0)
    return [sim.run_phase(src, dst, size, pol, plan=plan)
            for _ in range(n_phases)], plan


def test_pallas_kernel_on_agrees_with_off_on_a_reused_plan():
    """The sorted kernel over a plan's head plus the dense kernel over
    its background tail replay the cumsum-diff path in a phase.  From
    the second phase on, the carried queues amplify the cumsum-diff's
    own float32 rounding (1.3e-3 off float64 numpy here), so the kernel
    path is held to numpy there, at the same tolerance."""
    on, _ = _reused_plan_phases("on")
    off, _ = _reused_plan_phases("off", 1)
    ref, _ = _reused_plan_phases("off", backend="numpy")
    _assert_close(on[0], off[0], rtol=1e-4)
    for r_on, r_ref in zip(on, ref):
        _assert_close(r_on, r_ref, rtol=1e-4)


def test_segsum_counts_a_reused_plan_and_a_planless_phase():
    from repro.dragonfly import jax_backend

    def delta(run):
        before = dict(jax_backend.SEGSUM)
        out = run()
        return out, {k: v - before[k] for k, v in jax_backend.SEGSUM.items()}

    # 300 app + 16 background rows; 5 pair reductions a phase (4 sprays
    # + load_q); 2 link blocks
    (_, plan), reused = delta(lambda: _reused_plan_phases("on", 1))
    p_head = plan.device_bundle.p_sorted
    p_real = int(plan.pair_links.shape[0])
    assert reused == {"sorted_calls": 5, "dense_calls": 1 + 5,
                      "grid_steps": 2 + 5 * (p_head // 1024 + 2) + 5 * 2,
                      "head_pairs": p_real, "head_pad_pairs": p_head - p_real}

    src, dst, size = _flows(TWO_BLOCKS, seed=19, n=300)
    sim = DragonflySimulator(
        TWO_BLOCKS, SimParams(seed=5, backend="jax", pallas_kernel="on"))
    _, planless = delta(lambda: sim.run_phase(
        src, dst, size, RoutingPolicy(RoutingMode.ADAPTIVE_0)))
    # the whole pair list dense, padded to 4096-pair buckets: 4k pair
    # blocks x 2 link blocks a reduction
    assert planless["sorted_calls"] == 0
    assert planless["head_pairs"] == planless["head_pad_pairs"] == 0
    assert planless["dense_calls"] == 1 + 5
    assert planless["grid_steps"] > 2
    assert (planless["grid_steps"] - 2) % (5 * 4 * 2) == 0

    off_before = dict(jax_backend.SEGSUM)
    _reused_plan_phases("off", 1)
    assert jax_backend.SEGSUM == off_before


@pytest.mark.parametrize("n,n_app", [(300, 284), (1_000, 990), (129, 1)],
                         ids=["one_block", "straddles_blocks", "edge"])
def test_path_view_round_trips_and_takes_a_tail(n, n_app):
    """`path_view` and `from_path_view` are inverse on [n, ncand, hops]
    ids, and `_write_view` puts a tail's ids where `path_view` of the
    whole would hold them."""
    import jax.numpy as jnp

    from repro.dragonfly.jax_backend import (
        _write_view, from_path_view, path_view)

    rng = np.random.default_rng(3)
    ids = rng.integers(0, 5_000, (n, 6, 8)).astype(np.int32)
    view = path_view(jnp.asarray(ids))
    assert view.shape[1] == 128
    np.testing.assert_array_equal(
        np.asarray(from_path_view(view, ids.shape)), ids)
    stale = ids.copy()
    stale[n_app:] = 0
    written = _write_view(path_view(jnp.asarray(stale)),
                          jnp.asarray(ids[n_app:]), n_app)
    np.testing.assert_array_equal(np.asarray(written), np.asarray(view))


def test_path_gather_agrees_with_xla_gather_on_a_reused_plan(monkeypatch):
    """With the kernels on, two plan-reused phases through the Pallas
    path gather (the second writing its background tail into the donated
    index view) give the kernel outputs (w, rho, load_q, lat_us, s_flit)
    of XLA's gather through the same ids; `GATHER` counts the slots."""
    from repro.dragonfly import jax_backend

    real = jax_backend.fixed_point_jax

    def phases(max_links):
        monkeypatch.setattr(jax_backend, "PATH_GATHER_MAX_LINKS", max_links)
        seen = []

        def spy(sim, ctx):
            seen.append(real(sim, ctx))
            return seen[-1]

        monkeypatch.setattr(jax_backend, "fixed_point_jax", spy)
        before = dict(jax_backend.GATHER)
        _, plan = _reused_plan_phases("on")
        n_slots = plan.safe.size + 16 * plan.safe[0].size     # + background
        return seen, {k: v - before[k]
                      for k, v in jax_backend.GATHER.items()}, n_slots

    kern, kern_count, n_slots = phases(TWO_BLOCKS.n_links)
    xla, xla_count, _ = phases(TWO_BLOCKS.n_links - 1)
    assert kern_count == {"kernel_slots": 2 * 6 * n_slots, "xla_slots": 0}
    assert xla_count == {"kernel_slots": 0, "xla_slots": 2 * 6 * n_slots}
    assert len(kern) == len(xla) == 2
    for k_out, x_out in zip(kern, xla):
        for a, b in zip(k_out, x_out):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-9)


def test_pallas_kernel_auto_is_off_on_cpu():
    from repro.compat.runtime import on_tpu, resolve_pallas_kernel
    if not on_tpu():
        assert resolve_pallas_kernel("auto") is False
    assert resolve_pallas_kernel("on") is True
    assert resolve_pallas_kernel("off") is False
    with pytest.raises(ValueError):
        resolve_pallas_kernel("sometimes")


def test_pallas_kernel_knob_validated():
    with pytest.raises(ValueError):
        DragonflySimulator(TOPO, SimParams(pallas_kernel="maybe"))


# --------------------------------------------------------------------------
# Batched dispatch: run_phase_batch == per-sim run_phase.
# --------------------------------------------------------------------------
def _batch_calls(backend, n_sims=3, seed0=20, knob="auto"):
    calls = []
    for k in range(n_sims):
        sim = DragonflySimulator(TOPO, SimParams(
            seed=seed0 + k, backend=backend, pallas_kernel=knob))
        src, dst, size = _flows(TOPO, seed=seed0 + k)
        calls.append((sim, dict(src_nodes=src, dst_nodes=dst, bytes_=size,
                                policy=RoutingPolicy(
                                    RoutingMode.ADAPTIVE_0))))
    return calls


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_run_phase_batch_matches_sequential(backend):
    batched = [run_phase_batch([(sim, dict(kw))
                                for sim, kw in _batch_calls(backend)])
               for _ in range(1)][0]
    sequential = [sim.run_phase(**kw)
                  for sim, kw in _batch_calls(backend)]
    for rb, rs in zip(batched, sequential):
        assert np.array_equal(rb.t_us, rs.t_us)
        assert np.array_equal(rb.latency_us, rs.latency_us)
        assert np.array_equal(rb.flits, rs.flits)


def test_run_phase_batch_with_the_kernels_matches_sequential():
    """The vmapped dispatch runs the segment sums and the path gather
    lane by lane, each lane through its own path view and tables."""
    from repro.dragonfly import jax_backend
    before = dict(jax_backend.GATHER)
    batched = run_phase_batch([(sim, dict(kw)) for sim, kw in
                               _batch_calls("jax", knob="on")])
    assert jax_backend.GATHER["kernel_slots"] > before["kernel_slots"]
    sequential = [sim.run_phase(**kw)
                  for sim, kw in _batch_calls("jax", knob="on")]
    for rb, rs in zip(batched, sequential):
        _assert_close(rb, rs, rtol=1e-4)


def test_run_phase_batch_uses_one_vmapped_dispatch():
    from repro.dragonfly.jax_backend import PIPELINE_CALLS
    before = dict(PIPELINE_CALLS)
    run_phase_batch([(sim, kw) for sim, kw in _batch_calls("jax")])
    assert PIPELINE_CALLS["batched"] == before["batched"] + 1
    assert PIPELINE_CALLS["single"] == before["single"]


def test_run_phase_batch_of_faulted_phases_matches_sequential():
    """Phases with a fault candidate mask share one vmapped dispatch,
    the mask stacked like every other input, and match per-phase
    dispatch."""
    from repro.dragonfly.jax_backend import PIPELINE_CALLS

    def faulted():
        calls = _batch_calls("jax")
        for sim, _ in calls:
            sim.set_faults(FaultSchedule.of(
                link_down([1, TOPO.n_links // 2], start=0)))
        return calls

    before = dict(PIPELINE_CALLS)
    batched = run_phase_batch(faulted())
    assert PIPELINE_CALLS["batched"] == before["batched"] + 1
    assert PIPELINE_CALLS["single"] == before["single"]
    sequential = [sim.run_phase(**kw) for sim, kw in faulted()]
    for rb, rs in zip(batched, sequential):
        assert rb.stranded is not None
        assert np.array_equal(rb.stranded, rs.stranded)
        assert np.array_equal(rb.t_us, rs.t_us)
        assert np.array_equal(rb.latency_us, rs.latency_us)
        assert np.array_equal(rb.flits, rs.flits)


def _signed_inputs(sim, n_phases, plan=None):
    """(`batch_signature`, the pipeline inputs' shapes and dtypes with
    the statics) of ``n_phases`` phases, prepared and not run."""
    import jax

    from repro.dragonfly import jax_backend
    src, dst, size = _flows(TOPO, seed=42)
    out = []
    for _ in range(n_phases):
        ctx = sim._phase_begin(src, dst, size,
                               RoutingPolicy(RoutingMode.ADAPTIVE_0),
                               plan=plan)
        try:
            inputs, statics = jax_backend._prepare_inputs(sim, ctx)
            shapes = jax.tree.map(lambda a: (a.shape, a.dtype), inputs)
            out.append((jax_backend.batch_signature(sim, ctx),
                        (shapes, statics)))
        finally:
            ctx["stage"].stop()
    return out


def test_batch_signatures_agree_exactly_when_input_shapes_do():
    """One pair layout: over plan-reused phases with and without
    background rows, with `safe` as ids and as its index view, planless
    phases, and a plan whose background pairs fall on either side of
    the 1024-pair tail bucket's edge, two phases' `batch_signature`s
    are equal exactly when their pipeline inputs' shapes, dtypes and
    statics are."""
    src, dst, size = _flows(TOPO, seed=42)

    def sim(**kw):
        return DragonflySimulator(TOPO, SimParams(seed=1, backend="jax",
                                                  **kw))

    # 48 background flows draw 943-1,098 pairs a phase with this seed
    edge = sim(bg_flows_per_phase=48)
    edge_phases = _signed_inputs(edge, 4, edge.plan_for(src, dst, size))
    assert len({sig for sig, _ in edge_phases}) == 2
    quiet, view = sim(bg_enable=False), sim(pallas_kernel="on")
    phases = edge_phases + _signed_inputs(
        quiet, 2, quiet.plan_for(src, dst, size)) + _signed_inputs(
        view, 2, view.plan_for(src, dst, size)) + _signed_inputs(sim(), 2)
    for sig_a, shapes_a in phases:
        for sig_b, shapes_b in phases:
            assert (sig_a == sig_b) == (shapes_a == shapes_b)


# --------------------------------------------------------------------------
# Sweep lockstep: identical records, batched dispatch on jax.
# --------------------------------------------------------------------------
def _sweep(backend, lockstep):
    from repro.tenancy import TenancyMix, Workload, sweep
    mix = TenancyMix("mix2", (
        Workload("vic", "halo3d", 16, {"nx": 32, "vars_": 2},
                 arm=RoutingMode.ADAPTIVE_3),
        Workload("agg", "alltoall", 24, {"size_per_pair": 16384},
                 arm=RoutingMode.ADAPTIVE_0)))
    arms = {"min": RoutingMode.MIN_HASH, "ad3": RoutingMode.ADAPTIVE_3}
    return sweep(TOPO, [mix], arms, params=SimParams(backend=backend),
                 rounds=2, lockstep=lockstep)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_sweep_lockstep_matches_sequential(backend):
    seq = _sweep(backend, lockstep=False)
    lck = _sweep(backend, lockstep=True)
    assert len(seq) == len(lck) == 2
    for a, b in zip(seq, lck):
        for key in a:
            if isinstance(a[key], float):
                assert np.isclose(a[key], b[key], rtol=1e-12, atol=0.0)
            else:
                assert a[key] == b[key]


def test_sweep_lockstep_batches_the_column():
    from repro.dragonfly.jax_backend import PIPELINE_CALLS
    before = PIPELINE_CALLS["batched"]
    _sweep("jax", lockstep=True)
    assert PIPELINE_CALLS["batched"] > before
