"""The comparison that decides ``correct``.

After the window has closed, the plain reference (``bench/reference.py``)
builds its own plans from the cell's traffic and replays the simulator's
random stream from the seed over every phase the program ran.  At each
sampled phase it takes the program's carried link queues and estimate
memory from just before that phase (as a served model's check takes the
served tokens) and computes the phase itself, in float64, with its own
candidate paths and draws.  The numbers compared, each the worst over
the sampled phases:

  * ``t_rel_err``: largest relative gap of an app flow's Eq. (2) time;
  * ``lat_rel_err``: the same for its latency ``L``;
  * ``stall_abs_err``: largest gap of its stalls per flit ``s``;
  * ``queue_rel_err``: largest gap of the link queues carried into the
    next phase, over the reference's largest queue (floored at 1 ns);
  * whatever the traffic's kind adds (``bench/kinds/<kind>.py``), such
    as ``mode_mismatches`` where a policy decides.

``control=True`` also computes every sampled phase in bfloat16 and
reads the same numbers of that control against the float64 reference.
``free=True`` never re-syncs: every phase must be sampled, the reference
carries its own state from the first phase on and feeds its own (L, s)
to the kind's numbers; it shows why the check re-syncs.
"""

from __future__ import annotations

import numpy as np

from bench import reference

QUEUE_FLOOR_S = 1e-9


def _numbers(out: dict, ref: dict) -> dict:
    def rel(a, b):
        return float(np.max(np.abs(np.asarray(a) - b) / np.abs(b),
                            initial=0.0))

    q = ref["link_queue_s"]
    return {"t_rel_err": rel(out["t_us"], ref["t_us"]),
            "lat_rel_err": rel(out["latency_us"], ref["latency_us"]),
            "stall_abs_err": float(np.max(np.abs(
                np.asarray(out["stalls_per_flit"]) - ref["stalls_per_flit"]),
                initial=0.0)),
            "queue_rel_err": float(np.max(np.abs(out["link_queue_s"] - q))
                                   / max(float(np.max(np.abs(q))),
                                         QUEUE_FLOOR_S))}


def _worst(acc: dict, new: dict) -> None:
    for k, v in new.items():
        acc[k] = max(acc.get(k, 0.0), v)


def compare(driver, *, control: bool = False, free: bool = False,
            counts_for=()) -> dict:
    """Replay and compare.  Returns {"program": numbers, "control":
    numbers or None, "phases": compared phases, "flows": compared flows,
    "counts": {phase: (real pairs, rows)} for ``counts_for``,
    "by_phase": [t_rel_err of each compared phase]}."""
    cfg = driver.config
    sim, routing = cfg["sim"], cfg["routing"]
    stream, plans = driver.reference_plans()
    kept_all = driver.recorder.kept
    if free and len(kept_all) != driver.phases_run:
        raise ValueError("a free-running replay needs every phase sampled")
    ours = np.empty(0, np.int64) if driver.nodes is None else driver.nodes
    counts_for = set(counts_for)
    prog: dict = {}
    ctrl: dict = {}
    counts: dict = {}
    by_phase: list = []
    observed = {i: (res.latency_us, res.stalls_per_flit)
                for i, _, res in driver.recorder.decided}
    carried = None
    flows = 0
    for i in range(driver.phases_run):
        k = driver.plan_of(i)
        src, _, size = driver.phases[k]
        draws = stream.phase(len(src), ours)
        if i in counts_for:
            counts[i] = (int((plans[k] != reference.PAD).sum()
                             + (draws.bg_links != reference.PAD).sum()),
                         len(src) + len(draws.bg_src))
        kept = kept_all.get(i)
        if kept is not None:
            before = kept["before"] if carried is None else carried
            modes = kept["modes"] or [kept["mode"]] * len(src)
            args = (driver.mach, sim, routing, plans[k], size, src, modes,
                    draws, *before)
            ref = reference.run_phase(*args)
            if free:
                carried = (ref["link_queue_s"], ref["est_memory_s"])
                if i in observed:
                    observed[i] = (ref["latency_us"], ref["stalls_per_flit"])
            res = kept["result"]
            got = _numbers({"t_us": res.t_us, "latency_us": res.latency_us,
                            "stalls_per_flit": res.stalls_per_flit,
                            "link_queue_s": kept["after"][0]}, ref)
            _worst(prog, got)
            by_phase.append(got["t_rel_err"])
            if control:
                _worst(ctrl, _numbers(reference.run_phase(
                    *args, precision="bfloat16"), ref))
            flows += len(src)
        if driver.kind.HOST_DRAWS:
            stream.host_noise()
    prog.update(driver.kind.extra_numbers(driver, observed))
    return {"program": prog, "control": ctrl if control else None,
            "phases": len(kept_all), "flows": flows, "counts": counts,
            "by_phase": by_phase}


def verdict(numbers: dict, limits: dict) -> bool:
    """Every limited number was read and lies within its limit."""
    return bool(limits) and all(
        name in numbers and numbers[name] <= lim
        for name, lim in limits.items())
