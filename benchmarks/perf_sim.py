"""Simulator phase-kernel performance benchmark -> BENCH_sim.json.

Measures `DragonflySimulator.run_phase` wall-clock across backends on a
repeated heavy phase (the fig7/fig8/fig10 / train / serve shape: the
same traffic pattern, phase after phase):

  * reference   — the pre-refactor kernel (`repro.dragonfly.reference`),
                  the PR-3 baseline every speedup is measured against;
  * numpy       — the vectorized fast path, planless (candidates redrawn
                  per phase; seed-for-seed identical to reference);
  * numpy_plan  — fast path + PhasePlan reuse (the steady-state mode for
                  repeated collective rounds);
  * jax_plan    — the jitted backend; always run, and the run fails
                  unless the jitted pipeline really dispatched.

Emits the ``name,us_per_call,derived`` CSV rows all benchmarks print,
plus ``BENCH_sim.json`` at schema ``bench_sim/v2`` (documented in
docs/performance.md): per-backend phases/s, flows/s, per-stage timings,
and ``compile_s`` — the one-time first-call cost (jit tracing +
compilation on jax; cache warmup elsewhere) measured separately so
steady-state ``phase_s`` never includes it.  ``--smoke`` shrinks the
phase for CI.  Every number is a timing on whatever device jax finds
(``jax_device`` in the JSON names it).  Run as a script, it turns on
the persistent compilation cache (``repro.compat.enable_compile_cache``).
`make bench-perf` runs it and schema-checks the JSON via
``scripts/ci_lint.py --bench``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import numpy as np

from benchmarks.common import emit
from repro.compat import enable_compile_cache
from repro.core.strategies import RoutingMode
from repro.dragonfly import (DragonflySimulator, DragonflyTopology,
                             SimParams, TopologyParams)
from repro.dragonfly.jax_backend import PIPELINE_CALLS
from repro.dragonfly.reference import reference_run_phase
from repro.dragonfly.routing import RoutingPolicy
from repro.dragonfly.topology import make_allocation

SCHEMA = "bench_sim/v2"


def phase_inputs(topo: DragonflyTopology, n_flows: int, seed: int = 42):
    """A pareto-sized random many-to-many phase (alltoall-ish shape)."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, topo.params.n_nodes, size=n_flows)
    dst = (src + rng.integers(1, topo.params.n_nodes, size=n_flows)) \
        % topo.params.n_nodes
    size = rng.pareto(1.2, size=n_flows) * 65536 + 1024
    return src, dst, size


def _time_backend(topo, src, dst, size, alloc, *, phases, backend="numpy",
                  use_plans=False, reference=False, seed=0):
    params = SimParams(seed=seed, backend=backend,
                       profile_stages=not reference)
    sim = DragonflySimulator(topo, params)
    pol = RoutingPolicy(RoutingMode.ADAPTIVE_0)

    def one():
        if reference:
            return reference_run_phase(sim, src, dst, size, pol, alloc)
        plan = sim.plan_for(src, dst, size) if use_plans else None
        return sim.run_phase(src, dst, size, pol, alloc, plan=plan)

    t0 = time.perf_counter()
    one()                         # cold call: jit trace/compile, caches
    first_s = time.perf_counter() - t0
    one()                         # settle: second call is steady state
    sim.stage_time_s.clear()
    t0 = time.perf_counter()
    res = None
    for _ in range(phases):
        res = one()
    dt = (time.perf_counter() - t0) / phases
    compile_s = max(0.0, first_s - dt)
    stages = {k: v / phases for k, v in sim.stage_time_s.items()}
    return dt, compile_s, stages, res


def run(n_flows: int, phases: int, out_path: str | None):
    topo = DragonflyTopology(TopologyParams(n_groups=12))
    src, dst, size = phase_inputs(topo, n_flows)
    alloc = make_allocation(topo, min(64, n_flows), spread="inter_groups",
                            seed=3)
    arms = [("reference", dict(reference=True)),
            ("numpy", dict(backend="numpy")),
            ("numpy_plan", dict(backend="numpy", use_plans=True)),
            ("jax_plan", dict(backend="jax", use_plans=True))]
    calls_before = sum(PIPELINE_CALLS.values())
    results = {}
    checks = {}
    for name, kw in arms:
        dt, compile_s, stages, res = _time_backend(
            topo, src, dst, size, alloc, phases=phases, **kw)
        results[name] = {
            "phase_s": dt,
            "phases_per_s": 1.0 / dt,
            "flows_per_s": n_flows / dt,
            "compile_s": compile_s,
            "stages_s": stages,
        }
        checks[name] = res
        emit(f"perf_sim.{name}.phase", dt * 1e6,
             f"flows_per_s={n_flows / dt:.0f} compile_s={compile_s:.3f}")
    if sum(PIPELINE_CALLS.values()) <= calls_before:
        raise RuntimeError("jax_plan arm never dispatched the jitted "
                           "pipeline")

    # seed-equivalence sanity: the numpy fast path must replay the
    # reference bit-for-bit on the same seed (the golden-trace property)
    a, b = checks["reference"], checks["numpy"]
    seed_exact = bool(np.array_equal(a.t_us, b.t_us)
                      and np.array_equal(a.latency_us, b.latency_us))
    emit("perf_sim.check.numpy_seed_exact", 1.0 if seed_exact else 0.0, "")

    ref = results["reference"]["phase_s"]
    speedups = {f"{k}_vs_reference": ref / v["phase_s"]
                for k, v in results.items() if k != "reference"}
    for k, v in speedups.items():
        emit(f"perf_sim.speedup.{k}", v, "x")

    device = {"backend": jax.default_backend(),
              "n_devices": int(jax.device_count())}
    doc = {
        "schema": SCHEMA,
        "flows": int(n_flows),
        "phases_timed": int(phases),
        "topology": {"n_groups": 12, "n_links": int(topo.n_links)},
        "seed_exact": seed_exact,
        "jax_device": device,
        "backends": results,
        "speedup": speedups,
    }
    if out_path:
        pathlib.Path(out_path).write_text(json.dumps(doc, indent=2,
                                                     sort_keys=True) + "\n")
    return doc


def main(full: bool = False, smoke: bool = False,
         out: str | None = None) -> dict:
    n_flows, phases = (50_000, 5) if not smoke else (4_000, 3)
    if full:
        n_flows, phases = 120_000, 5
    return run(n_flows, phases, out)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small CI pass (4k flows)")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale pass (120k flows)")
    ap.add_argument("--out", default="BENCH_sim.json",
                    help="output JSON path (default: BENCH_sim.json)")
    args = ap.parse_args()
    enable_compile_cache()
    main(full=args.full, smoke=args.smoke, out=args.out)
