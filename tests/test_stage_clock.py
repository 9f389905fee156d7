"""The simulator's stage clock and the jax engine's transfer counters.

``SimParams.profile_stages`` turns on one mechanism: each stage of a
phase adds its host seconds into ``sim.stage_time_s`` and is the
profiler span ``df.<stage>`` over the same extent, carrying the phase's
index.  On the jax backend ``transfer``, ``device_wait`` and ``fetch``
nest inside ``fixed_point``; ``policy`` times the routing policy around
a phase.  ``jax_backend.TRANSFER`` counts host<->device copies and bytes
always.  The benchmark's per-layer metrics read both
(``bench/metrics/``).
"""

import importlib.util
import pathlib

import numpy as np
import pytest

from repro.core.strategies import RoutingMode
from repro.dragonfly import (DragonflySimulator, DragonflyTopology,
                             SimParams, TopologyParams)
from repro.dragonfly import jax_backend
from repro.dragonfly.routing import RoutingPolicy
from repro.dragonfly.topology import make_allocation

ROOT = pathlib.Path(__file__).resolve().parent.parent
TOPO = DragonflyTopology(TopologyParams(n_groups=4, chassis_per_group=2,
                                        blades_per_chassis=4))
POL = RoutingPolicy(RoutingMode.ADAPTIVE_0)
OLD_STAGES = ("candidates", "estimate", "fixed_point", "finalize")
SUB_STAGES = ("transfer", "device_wait", "fetch")
COUNTS = ("h2d_copies", "h2d_bytes", "d2h_bytes")


def _flows(seed=42, n=300):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, TOPO.n_nodes, size=n)
    dst = (src + rng.integers(1, TOPO.n_nodes, size=n)) % TOPO.n_nodes
    size = rng.pareto(1.2, size=n) * 65536 + 1024
    return src, dst, size


def _sim(profile: bool, backend="jax", seed=3):
    return DragonflySimulator(TOPO, SimParams(seed=seed, backend=backend,
                                              profile_stages=profile))


def _transfer_delta(fn) -> dict:
    before = dict(jax_backend.TRANSFER)
    fn()
    return {k: jax_backend.TRANSFER[k] - before[k] for k in before}


class _SpanLog(list):
    """The spans entered, as (name, arguments); ``closed``: the names of
    those exited, in order."""

    def __init__(self):
        super().__init__()
        self.closed: list = []


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each span
    entered, with its arguments, and each exited."""

    def __init__(self, log):
        self.log = log

    def __call__(self, name, **kw):
        rec = self

        class Span:
            def __enter__(self):
                rec.log.append((name, kw))
                return self

            def __exit__(self, *exc):
                rec.log.closed.append(name)
        return Span()


@pytest.fixture
def spans(monkeypatch):
    import jax
    log = _SpanLog()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder(log))
    return log


def test_jax_phase_records_nested_stages():
    src, dst, size = _flows()
    sim = _sim(True)
    plan = sim.plan_for(src, dst, size)
    sim.run_phase(src, dst, size, POL, plan=plan)
    sim.stage_time_s.clear()
    sim.run_phase(src, dst, size, POL, plan=plan)
    st = sim.stage_time_s
    assert set(OLD_STAGES + SUB_STAGES) <= set(st)
    assert all(st[k] > 0 for k in OLD_STAGES + SUB_STAGES)
    assert sum(st[k] for k in SUB_STAGES) <= st["fixed_point"]


def test_numpy_phase_keeps_the_four_stages():
    src, dst, size = _flows()
    sim = _sim(True, backend="numpy")
    sim.run_phase(src, dst, size, POL)
    assert set(sim.stage_time_s) == set(OLD_STAGES)


def test_spans_name_each_stage_with_its_phase(spans):
    src, dst, size = _flows()
    sim = _sim(True)
    plan = sim.plan_for(src, dst, size)
    for _ in range(2):
        sim.run_phase(src, dst, size, POL, plan=plan)
    names = [n for n, _ in spans]
    assert names == [f"df.{s}" for s in
                     ("candidates", "estimate", "fixed_point", *SUB_STAGES,
                      "finalize")] * 2
    assert [kw for _, kw in spans] == [{"phase": 0}] * 7 + [{"phase": 1}] * 7


def test_profile_off_reads_no_clock_and_enters_no_span(spans):
    src, dst, size = _flows()
    sim = _sim(False)
    plan = sim.plan_for(src, dst, size)
    for _ in range(2):
        sim.run_phase(src, dst, size, POL, plan=plan)
    assert sim.stage_time_s == {}
    assert spans == []


def test_profile_off_never_waits_for_the_device(monkeypatch):
    import jax
    waits = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waits.append(x) or x)
    src, dst, size = _flows()
    for profile in (False, True):
        _sim(profile).run_phase(src, dst, size, POL)
        assert len(waits) == int(profile)


def test_steady_phases_move_equal_counts():
    src, dst, size = _flows()
    sim = _sim(False)
    plan = sim.plan_for(src, dst, size)
    sim.run_phase(src, dst, size, POL, plan=plan)
    deltas = [_transfer_delta(
        lambda: sim.run_phase(src, dst, size, POL, plan=plan))
        for _ in range(2)]
    assert deltas[0] == deltas[1]
    assert all(deltas[0][k] > 0 for k in COUNTS)


def test_steady_phase_counts_every_input_copy():
    """7 background tails, 12 per-phase arrays and 8 scalars; the five
    outputs (w, rho, load_q, lat_us, s_flit) come back as float32."""
    src, dst, size = _flows()
    sim = _sim(False)
    plan = sim.plan_for(src, dst, size)
    sim.run_phase(src, dst, size, POL, plan=plan)
    d = _transfer_delta(lambda: sim.run_phase(src, dst, size, POL,
                                              plan=plan))
    n_all = len(size) + sim.params.bg_flows_per_phase
    assert d["h2d_copies"] == 7 + 12 + 8
    assert d["d2h_bytes"] == 4 * (n_all * 6 + 2 * TOPO.n_links + 2 * n_all)


def test_pinning_a_new_plan_counts_as_a_steady_phase():
    """The one-off plan pinning stays out of the per-phase counters."""
    src, dst, size = _flows()
    sim = _sim(False)
    plan = sim.plan_for(src, dst, size)
    sim.run_phase(src, dst, size, POL, plan=plan)
    steady = _transfer_delta(
        lambda: sim.run_phase(src, dst, size, POL, plan=plan))
    src2, dst2, size2 = _flows(seed=43)
    plan2 = sim.plan_for(src2, dst2, size2)
    first = _transfer_delta(
        lambda: sim.run_phase(src2, dst2, size2, POL, plan=plan2))
    assert first == steady
    assert plan2.device_bundle is not None


def test_batched_dispatch_records_stages_on_its_first_sim():
    from repro.dragonfly.simulator import run_phase_batch
    src, dst, size = _flows()
    sims = [_sim(True, seed=s) for s in (3, 4)]
    calls = [(s, dict(src_nodes=src, dst_nodes=dst, bytes_=size,
                      policy=POL)) for s in sims]
    run_phase_batch(calls)
    assert set(SUB_STAGES) <= set(sims[0].stage_time_s)
    assert not set(SUB_STAGES) & set(sims[1].stage_time_s)
    for s in sims:
        assert set(OLD_STAGES) <= set(s.stage_time_s)


def test_policy_stage_for_run_iteration_engine(spans):
    from repro.dragonfly.traffic import engine_for_arm, run_iteration_engine
    from repro.policy import AppAwareConfig
    sim = _sim(True, backend="numpy")
    alloc = make_allocation(TOPO, 8, spread="inter_groups", seed=1)
    phases = [(np.arange(8), np.roll(np.arange(8), 1),
               np.full(8, 65536.0))] * 2
    engine = engine_for_arm("app_aware", sim, AppAwareConfig(), seed=0)
    run_iteration_engine(sim, alloc, phases, engine, use_plans=True)
    assert sim.stage_time_s["policy"] > 0
    policy = [kw["phase"] for n, kw in spans if n == "df.policy"]
    assert policy == [0, 0, 1, 1]        # decide, then publish, per phase


def test_decide_and_publish_nest_in_the_policy_stage(spans):
    from repro.dragonfly.traffic import engine_for_arm, run_iteration_engine
    from repro.policy import AppAwareConfig
    sim = _sim(True, backend="numpy")
    alloc = make_allocation(TOPO, 8, spread="inter_groups", seed=1)
    phases = [(np.arange(8), np.roll(np.arange(8), 1),
               np.full(8, 65536.0))] * 2
    engine = engine_for_arm("app_aware", sim, AppAwareConfig(), seed=0)
    run_iteration_engine(sim, alloc, phases, engine, use_plans=True)
    st = sim.stage_time_s
    assert 0 < st["decide"] + st["publish"] <= st["policy"]
    policy = [(n, kw["phase"]) for n, kw in spans
              if n in ("df.policy", "df.decide", "df.publish")]
    assert policy == [("df.policy", 0), ("df.decide", 0),
                      ("df.policy", 0), ("df.publish", 0),
                      ("df.policy", 1), ("df.decide", 1),
                      ("df.policy", 1), ("df.publish", 1)]
    closed = [n for n in spans.closed if n in ("df.policy", "df.decide",
                                                "df.publish")]
    assert closed == ["df.decide", "df.policy", "df.publish",
                      "df.policy"] * 2            # each inside its policy


def test_policy_stage_for_the_tenancy_round(spans):
    from repro.tenancy import InterferenceEngine, TenancyMix, Workload
    mix = TenancyMix("mix", (
        Workload("vic", "halo3d", 8, {"nx": 32, "vars_": 2},
                 arm="app_aware"),
        Workload("agg", "alltoall", 8, {"size_per_pair": 16384})))
    params = SimParams(seed=1, profile_stages=True, bg_enable=False)
    InterferenceEngine(TOPO, params).run_mix(mix, rounds=2,
                                             baselines=False)
    policy = [kw["phase"] for n, kw in spans if n == "df.policy"]
    assert policy == [0, 0, 1, 1]        # decide, then publish, per round


def test_argument_error_opens_no_stage(spans):
    src, dst, size = _flows()
    sim = _sim(True)
    with pytest.raises(ValueError):
        sim.run_phase(src, dst, size, POL, allocation=object(),
                      tenants=object())
    assert spans == [] and sim.stage_time_s == {}


def test_a_raising_phase_closes_its_open_stage(spans, monkeypatch):
    src, dst, size = _flows()
    sim = _sim(True)

    def kernel(ctx):
        raise RuntimeError("kernel")
    monkeypatch.setattr(sim, "_run_kernel", kernel)
    with pytest.raises(RuntimeError):
        sim.run_phase(src, dst, size, POL)
    names = [n for n, _ in spans]
    assert names == ["df.candidates", "df.estimate", "df.fixed_point"]
    assert spans.closed == names
    assert set(sim.stage_time_s) == {"candidates", "estimate",
                                     "fixed_point"}


def test_a_raising_batch_closes_every_open_stage(spans):
    from repro.dragonfly.simulator import run_phase_batch
    src, dst, size = _flows()
    kw = dict(src_nodes=src, dst_nodes=dst, bytes_=size, policy=POL)
    bad = dict(kw, allocation=object(), tenants=object())
    with pytest.raises(ValueError):
        run_phase_batch([(_sim(True, seed=3), kw), (_sim(True, seed=4), bad)])
    assert [n for n, _ in spans] == ["df.candidates", "df.estimate",
                                     "df.fixed_point"]
    assert spans.closed == [n for n, _ in spans]


def _load_trace_module():
    spec = importlib.util.spec_from_file_location("bench_trace",
                                                  ROOT / "bench" / "trace.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cpu_capture_holds_the_spans(tmp_path):
    """A profiler capture on the CPU: the sub-stage spans reach the host
    plane by their bare names, nested in time inside the phase's
    ``df.fixed_point``, each with a ``phase`` stat."""
    from jax.profiler import ProfileData
    trace = _load_trace_module()
    src, dst, size = _flows()
    sim = _sim(True)
    plan = sim.plan_for(src, dst, size)
    sim.run_phase(src, dst, size, POL, plan=plan)
    with trace.capture(tmp_path):
        sim.run_phase(src, dst, size, POL, plan=plan)
    host = [(n, s, e) for p, _, n, s, e in trace.load(tmp_path)
            if p == trace.HOST_PLANE and n.startswith("df.")]
    by = {}
    for n, s, e in host:
        by.setdefault(n, []).append((s, e))
    assert all(len(by[f"df.{k}"]) == 1 for k in OLD_STAGES + SUB_STAGES)
    (fs, fe), = by["df.fixed_point"]
    for k in SUB_STAGES:
        (s, e), = by[f"df.{k}"]
        assert fs <= s <= e <= fe
    (path,) = tmp_path.rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    phase = {}
    for plane in data.planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("df."):
                    phase[ev.name] = dict(ev.stats).get("phase")
    assert {phase[f"df.{k}"] for k in SUB_STAGES} == {1}
