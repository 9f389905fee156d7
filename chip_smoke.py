"""Drive the jax phase engine once on a TPU, at full Aries width.

    python3 chip_smoke.py [--seed N]

The main path is ``DragonflySimulator.run_phase`` with
``SimParams(backend="jax")`` and the default ``pallas_kernel="auto"``,
which on a TPU runs the Pallas segment-sum compiled:

  * machine: the default Aries machine (12 groups, 56,448 links);
  * traffic: the ``benchmarks/perf_sim.py`` many-to-many phase at
    120,000 app flows (``SimParams.max_flows``, so nothing is
    subsampled) plus the 16 background flows: 120,016 rows;
  * mode: ``ADAPTIVE_0``, replayed through ``sim.plan_for`` plan reuse.

One cold and a few steady phases run with ``pallas_kernel="auto"`` and
again with ``"off"``; each is held to the numpy backend on the same
seed within the pinned ``rtol=2e-2``.  Smaller phases on the same
machine cover the other in-graph branches: a faulted phase (candidate
mask), a notification-active phase, and one lockstep tenancy column
(the vmapped ``run_phase_batch`` dispatch).

Earlier lines print labelled numbers, each tagged with the device it
ran on; the last line is one JSON object
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Any failed phase raises, so the script exits nonzero.  It has no CPU
mode: without a TPU it exits 1 before running anything.  All traffic
comes from ``--seed``; nothing is downloaded.  The persistent compile
cache lives where ``repro.compat.enable_compile_cache`` puts it.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.perf_sim import phase_inputs  # noqa: E402
from repro.compat import enable_compile_cache, resolve_pallas_kernel  # noqa: E402
from repro.core.strategies import RoutingMode  # noqa: E402
from repro.dragonfly import (DragonflySimulator, SimParams,  # noqa: E402
                             TopologyParams)
from repro.dragonfly.jax_backend import PIPELINE_CALLS, kernel_mode  # noqa: E402
from repro.dragonfly.routing import RoutingPolicy  # noqa: E402
from repro.dragonfly.topology import DragonflyTopology  # noqa: E402
from repro.faults import FaultSchedule, link_down  # noqa: E402
from repro.tenancy import TenancyMix, Workload, sweep  # noqa: E402

#: float32 pipeline vs float64 numpy, as pinned in
#: tests/test_dragonfly_fastpath.py
JAX_RTOL = 2e-2
STALL_ATOL = 1e-4
#: full width: the default Aries machine at SimParams.max_flows
MAIN_FLOWS = SimParams().max_flows
STEADY_PHASES = 3
#: the smaller phases: one compiled signature each
SMALL_FLOWS = 4096
#: the simulator state a phase carries into the next one
CARRIED = ("link_queue_s", "est_memory_s", "link_notify_age")


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _calls() -> int:
    return sum(PIPELINE_CALLS.values())


def paired_phases(jax_sim, numpy_sim, src, dst, size, n_phases: int, *,
                  mode=RoutingMode.ADAPTIVE_0) -> dict:
    """``n_phases`` plan-reused phases on both backends, phase by phase.

    Before each phase the numpy simulator takes the jax one's carried
    state, so the comparison holds one phase of the engine to numpy
    instead of float32 differences compounded through the queue
    carry-over.  Both simulators share a seed and so draw the same
    plan, background flows and noise.  The jax results are host numpy
    arrays, so each jax timing ends after the device finished.  With
    ``profile_stages`` on, ``stages_s`` is the jax simulator's stage
    split averaged over the phases after the first."""
    pol = RoutingPolicy(mode)
    plans = [sim.plan_for(src, dst, size) for sim in (jax_sim, numpy_sim)]
    out = {"jax": [], "numpy": [], "jax_s": [], "numpy_s": [],
           "plan": plans[0]}
    before = _calls()
    for phase in range(n_phases):
        if phase == 1:
            jax_sim.stage_time_s.clear()
        for name in CARRIED:
            getattr(numpy_sim, name)[:] = getattr(jax_sim, name)
        for key, sim, plan in zip(("jax", "numpy"), (jax_sim, numpy_sim),
                                  plans):
            t0 = time.perf_counter()
            out[key].append(sim.run_phase(src, dst, size, pol, plan=plan))
            out[key + "_s"].append(time.perf_counter() - t0)
    out["calls"] = _calls() - before
    _check(out["calls"] == n_phases,
           f"{out['calls']} jitted dispatches for {n_phases} phases")
    out["stages_s"] = {k: v / max(1, n_phases - 1)
                       for k, v in jax_sim.stage_time_s.items()}
    return out


def parity(jax_results, numpy_results) -> dict:
    """Hold jax phases to numpy ones; the largest errors seen.

    Raises past the pinned tolerance (Eq. (2) times and latencies at
    ``rtol``, stalls at ``rtol`` plus ``atol``)."""
    rel, stall = 0.0, 0.0
    for rj, rn in zip(jax_results, numpy_results, strict=True):
        for name in ("t_us", "latency_us"):
            a, b = getattr(rj, name), getattr(rn, name)
            _check(np.all(np.isfinite(a)), f"non-finite {name}")
            np.testing.assert_allclose(a, b, rtol=JAX_RTOL, err_msg=name)
            nz = b != 0
            rel = max(rel, float(np.max(np.abs(a - b)[nz] / np.abs(b[nz]),
                                        initial=0.0)))
        a, b = rj.stalls_per_flit, rn.stalls_per_flit
        np.testing.assert_allclose(a, b, rtol=JAX_RTOL, atol=STALL_ATOL,
                                   err_msg="stalls_per_flit")
        stall = max(stall, float(np.max(np.abs(a - b))))
    return {"max_rel_err": rel, "stall_max_abs_err": stall}


def run_main(topo, n_flows: int, *, seed: int, steady: int) -> dict:
    """The main phase, once per ``pallas_kernel`` in ("auto", "off"),
    each held to numpy on the same seed."""
    src, dst, size = phase_inputs(topo, n_flows, seed=seed)
    out = {"links": int(topo.n_links)}
    for knob in ("auto", "off"):
        params = SimParams(seed=seed, backend="jax", pallas_kernel=knob,
                           profile_stages=True)
        run = paired_phases(DragonflySimulator(topo, params),
                            DragonflySimulator(topo, SimParams(seed=seed)),
                            src, dst, size, 1 + steady)
        secs = run["jax_s"]
        steady_s = float(np.mean(secs[1:]))
        use_kernel, interpret = kernel_mode(params)
        out[knob] = {"use_kernel": use_kernel, "interpret": interpret,
                     "pipeline_calls": run["calls"],
                     "first_s": secs[0], "steady_s_per_phase": steady_s,
                     "compile_s": secs[0] - steady_s,
                     "numpy_s_per_phase": float(np.mean(run["numpy_s"])),
                     "stages_s": run["stages_s"],
                     **parity(run["jax"], run["numpy"])}
    plan = run["plan"]
    out["rows"] = int(plan.device_bundle.bufs[0].shape[0])
    out["pairs"] = int(plan.pair_links.shape[0])
    return out


def run_faulted(topo, n_flows: int, *, seed: int, n_phases: int = 2) -> dict:
    """Plan-reused phases with dead global links (in-graph cand_mask)."""
    src, dst, size = phase_inputs(topo, n_flows, seed=seed + 1)
    sched = FaultSchedule.of(link_down(n_random=max(2, topo.n_links // 200),
                                       seed=seed + 2))
    sims = [DragonflySimulator(topo, SimParams(seed=seed, backend=b))
            for b in ("jax", "numpy")]
    for sim in sims:
        sim.set_faults(sched)
    run = paired_phases(*sims, src, dst, size, n_phases,
                        mode=RoutingMode.ADAPTIVE_3)
    for rj, rn in zip(run["jax"], run["numpy"]):
        _check(rj.stranded is not None, "faulted: no fault was active")
        _check(np.array_equal(rj.stranded, rn.stranded),
               "faulted: stranded flows differ from numpy")
    return {"stranded": int(run["jax"][-1].n_stranded),
            **parity(run["jax"], run["numpy"])}


def run_notifying(topo, n_flows: int, *, seed: int,
                  n_phases: int = 3) -> dict:
    """Plan-reused phases with the congestion-notification channel on;
    from the second phase on, raised flags penalize candidates."""
    src, dst, size = phase_inputs(topo, n_flows, seed=seed + 3)
    sims = [DragonflySimulator(topo, SimParams(
        seed=seed, backend=b, notify_threshold_s=1e-5,
        notify_penalty_s=300e-6)) for b in ("jax", "numpy")]
    run = paired_phases(*sims, src, dst, size, n_phases,
                        mode=RoutingMode.ADAPTIVE_2)
    exposure = max(float(r.notified.max()) for r in run["jax"])
    _check(exposure > 0.0, "notifying: no flow crossed a flagged link")
    return {"max_notified": exposure, **parity(run["jax"], run["numpy"])}


def run_lockstep(topo, *, seed: int, ranks: int = 16) -> dict:
    """One lockstep tenancy column: two victim arms of a one-tenant mix
    advance through ``run_phase_batch``'s vmapped dispatch."""
    mix = TenancyMix("smoke", (Workload("vic", "halo3d", ranks,
                                        {"nx": 32, "vars_": 2},
                                        arm=RoutingMode.ADAPTIVE_3),))
    arms = {"min": RoutingMode.MIN_HASH, "ad3": RoutingMode.ADAPTIVE_3}
    before = PIPELINE_CALLS["batched"]
    recs = sweep(topo, [mix], arms, params=SimParams(seed=seed,
                                                     backend="jax"),
                 rounds=1, seed=seed, lockstep=True)
    batched = PIPELINE_CALLS["batched"] - before
    _check(batched > 0, "lockstep: no vmapped dispatch ran")
    ref = sweep(topo, [mix], arms, params=SimParams(seed=seed), rounds=1,
                seed=seed, lockstep=False)
    rel = 0.0
    for a, b in zip(recs, ref, strict=True):
        for key in ("victim_time_us", "victim_alone_us"):
            np.testing.assert_allclose(a[key], b[key], rtol=JAX_RTOL,
                                       err_msg=key)
            rel = max(rel, abs(a[key] - b[key]) / abs(b[key]))
    return {"cells": len(recs), "batched_calls": batched, "max_rel_err": rel}


def _fmt(d: dict) -> str:
    return " ".join(f"{k}={v!r}" for k, v in d.items()
                    if not isinstance(v, dict))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; jax found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    tag = f"[{dev.platform}:{dev.device_kind}]"
    cache = enable_compile_cache()
    print(f"{tag} jax={jax.__version__} devices={len(jax.devices())} "
          f"compile_cache={cache}", flush=True)
    _check(resolve_pallas_kernel("auto"),
           "pallas_kernel='auto' does not pick the kernel on this TPU")

    topo = DragonflyTopology(TopologyParams(n_groups=12))
    main_out = run_main(topo, MAIN_FLOWS, seed=args.seed,
                        steady=STEADY_PHASES)
    _check((main_out["auto"]["use_kernel"], main_out["auto"]["interpret"])
           == (True, False), "auto: Pallas kernel not compiled")
    print(f"{tag} main {_fmt(main_out)}", flush=True)
    for knob in ("auto", "off"):
        print(f"{tag} main.{knob} {_fmt(main_out[knob])}", flush=True)
        print(f"{tag} main.{knob}.stages_s_per_phase "
              f"{_fmt(main_out[knob]['stages_s'])}", flush=True)
    for name, fn in (("faulted", run_faulted),
                     ("notifying", run_notifying)):
        print(f"{tag} {name} flows={SMALL_FLOWS} "
              f"{_fmt(fn(topo, SMALL_FLOWS, seed=args.seed))}", flush=True)
    print(f"{tag} lockstep {_fmt(run_lockstep(topo, seed=args.seed))}",
          flush=True)
    stats = dev.memory_stats() or {}
    print(f"{tag} peak_bytes_in_use={stats.get('peak_bytes_in_use')}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
