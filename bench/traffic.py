"""A cell's traffic and the loop its window drives.

A traffic mix is a data file, ``bench/traffic/<mix>.json``.  Its
``kind`` names the code that reads it, ``bench/kinds/<kind>.py``, which
draws the mix's phases from the run's seed and steps the window's loop
(see ``bench/kinds/__init__.py``); a job's collective ``pattern`` is
``bench/patterns/<pattern>.py`` and the machine's path model
``bench/machines/<family>.py``.  All are found by name, so a new mix of
an existing kind is one data file, and a new kind, pattern or machine
family is one new file.

Every seed gives the same amount of work: each plan's pair count, as the
machine's path model counts it, lies in the cell's ``plan_pairs`` band,
so one compiled shape serves every seed.  The program only receives the
generated inputs.
"""

from __future__ import annotations

import importlib
import json
import pathlib

import numpy as np

from bench import reference

BENCH = pathlib.Path(__file__).resolve().parent


def load_json(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``, found by name."""
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def kind_of(mix: dict):
    return importlib.import_module(f"bench.kinds.{mix['kind']}")


def pattern_of(name: str):
    return importlib.import_module(f"bench.patterns.{name}")


def pairs_per_flow(plan) -> np.ndarray:
    """Real (link, flow candidate) pairs of each flow of a plan."""
    return (plan != reference.PAD).sum(axis=(1, 2))


def check_band(mach, sim: dict, seed: int, phases, band) -> None:
    """Raise unless every phase's plan, drawn from the seed's stream as
    the simulator draws it, has its pair count in ``band``."""
    stream = reference.Stream(mach, sim, seed)
    pairs = [int(pairs_per_flow(stream.candidates(s, d)).sum())
             for s, d, _ in phases]
    if not all(band[0] <= n <= band[1] for n in pairs):
        raise RuntimeError(f"plan pairs {pairs} outside the band {band}")


class Recorder:
    """Wraps one simulator's ``run_phase`` and keeps what the check needs:
    the carried state before and after each sampled phase with its
    result, and the mode and result of every phase a policy decided."""

    def __init__(self, sim, sampled):
        self.sim, self.sampled = sim, sampled
        self._run = sim.run_phase
        sim.run_phase = self.run_phase
        self.n = 0
        self.kept: dict = {}
        self.decided: list = []

    def run_phase(self, src, dst, bytes_, policy, allocation=None,
                  modes=None, plan=None, tenants=None):
        i, sim = self.n, self.sim
        self.n += 1
        keep = self.sampled(i)
        if keep:
            before = (sim.link_queue_s.copy(), sim.est_memory_s.copy())
        res = self._run(src, dst, bytes_, policy, allocation=allocation,
                        modes=modes, plan=plan, tenants=tenants)
        if keep:
            self.kept[i] = {"before": before, "result": res,
                            "mode": policy.mode.value,
                            "modes": None if modes is None
                            else [m.value for m in modes],
                            "after": (sim.link_queue_s.copy(),
                                      sim.est_memory_s.copy())}
        if modes is not None:
            self.decided.append((i, modes[0].value, res))
        return res


class Driver:
    """Builds a cell's simulator from its traffic and steps the window's
    loop.  ``step()`` runs one unit of work and returns its phases."""

    def __init__(self, mix: dict, config: dict, seed: int, band,
                 sim_params: dict, sampled):
        from repro.dragonfly import DragonflySimulator, SimParams, \
            make_topology

        self.mix, self.config, self.seed = mix, config, seed
        self.kind = kind_of(mix)
        self.mach = reference.machine(config)
        self.phases, self.nodes = self.kind.draw(mix, self.mach,
                                                 config["sim"], seed, band)
        topo = make_topology(config["topology"])
        self.sim = DragonflySimulator(topo, SimParams(seed=seed,
                                                      **sim_params))
        self.plans = [self.sim.plan_for(s, d, b) for s, d, b in self.phases]
        self.recorder = Recorder(self.sim, sampled)
        self.loop = self.kind.Loop(self)

    def policy(self, mode: str):
        """The program's routing policy for one mode of the config."""
        from repro.core.strategies import RoutingMode
        from repro.dragonfly.routing import RoutingPolicy
        r = self.config["routing"]
        return RoutingPolicy(RoutingMode(mode), bias_unit_s=r["bias_unit_s"],
                             spray_temperature_s=r["spray_temperature_s"],
                             hop_latency_s=r["hop_latency_s"])

    @property
    def phases_run(self) -> int:
        return self.recorder.n

    def plan_of(self, i: int) -> int:
        """Index of the plan (and phase pattern) of the i-th phase."""
        return i % len(self.phases)

    def step(self) -> int:
        return self.loop.step()

    def reference_plans(self):
        """The reference's stream from the seed, left where the program's
        is once its plans are built, and the reference's plans."""
        stream = reference.Stream(self.mach, self.config["sim"], self.seed)
        return stream, [stream.candidates(s, d) for s, d, _ in self.phases]
