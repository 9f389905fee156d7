"""One job of ``ranks`` ranks placed over ``groups`` groups, whose
collective ``pattern`` is run round after round under the paper's §5
protocol: each round runs the job's phases once under every entry of
``modes``, in turn (a routing mode, or ``"app_aware"`` for the paper's
Algorithm 1 as a ``PolicyEngine``)."""

from __future__ import annotations

import numpy as np

from bench import reference, traffic

HOST_DRAWS = True


def placement(mach, n_ranks: int, k: int, rng) -> np.ndarray:
    """Ranks packed into k random groups, rank i in group i mod k (the
    paper's production-style placement, Fig. 8)."""
    npg = mach.nodes_per_group
    k = min(mach.n_groups, max(min(k, mach.n_groups), -(-n_ranks // npg)))
    gs = rng.choice(mach.n_groups, size=k, replace=False)
    pool = np.stack([g * npg + rng.permutation(npg) for g in gs])
    return pool.T.ravel()[:n_ranks].astype(np.int64)


def draw(mix: dict, mach, sim: dict, seed: int, band):
    rng = np.random.default_rng([seed, 0])
    nodes = placement(mach, mix["ranks"], mix["groups"], rng)
    pattern = traffic.pattern_of(mix["pattern"])
    phases = [(nodes[s], nodes[d], b) for s, d, b in
              pattern.phases(mix["ranks"], **mix["pattern_args"])]
    if band is not None:
        traffic.check_band(mach, sim, seed, phases, band)
    return phases, nodes


class Loop:
    def __init__(self, driver):
        from repro.core.strategies import RoutingMode
        from repro.dragonfly.topology import Allocation
        from repro.dragonfly.traffic import engine_for_arm
        from repro.policy import AppAwareConfig

        mix = driver.mix
        self.driver, self.units = driver, 0
        self.rank_phases = traffic.pattern_of(mix["pattern"]).phases(
            mix["ranks"], **mix["pattern_args"])
        self.alloc = Allocation("bench-job",
                                tuple(int(n) for n in driver.nodes))
        self.modes = [m if m == "app_aware" else driver.policy(m)
                      for m in mix["modes"]]
        pol = dict(mix["policy"])
        for k in ("mode_a", "mode_b", "mode_a_alltoall"):
            pol[k] = RoutingMode(pol[k])
        self.engine = engine_for_arm(
            "app_aware", driver.sim, AppAwareConfig(**pol),
            seed=driver.seed) if "app_aware" in mix["modes"] else None
        self.base = driver.policy(mix["base_mode"])

    def step(self) -> int:
        from repro.dragonfly.traffic import (PATTERN_KIND, run_iteration,
                                             run_iteration_engine)
        d, mix = self.driver, self.driver.mix
        mode = self.modes[self.units % len(self.modes)]
        if mode == "app_aware":
            run_iteration_engine(d.sim, self.alloc, self.rank_phases,
                                 self.engine, site=mix["pattern"],
                                 kind=PATTERN_KIND[mix["pattern"]],
                                 base_policy=self.base, use_plans=True)
        else:
            run_iteration(d.sim, self.alloc, self.rank_phases, mode,
                          use_plans=True)
        self.units += 1
        return len(self.rank_phases)


def extra_numbers(driver, observed) -> dict:
    """``mode_mismatches``: phases in which the program's policy chose
    another mode than the reference's Algorithm 1, fed each decided
    phase's ``observed`` (latency in us, stalls per flit)."""
    if "app_aware" not in driver.mix["modes"]:
        return {}
    alg = reference.Algorithm1(
        driver.mix["policy"],
        alltoall=traffic.pattern_of(driver.mix["pattern"]).ALLTOALL)
    clk = driver.config["sim"]["nic_clock_ghz"]
    wrong = 0
    for i, mode, _ in driver.recorder.decided:
        _, _, size = driver.phases[driver.plan_of(i)]
        wrong += alg.decide(int(np.max(size))) != mode
        lat_us, stalls = observed[i]
        alg.observe(mode, float(np.mean(np.asarray(lat_us, np.float64)
                                        * clk * 1e3)),
                    float(np.mean(np.asarray(stalls, np.float64))))
    return {"mode_mismatches": wrong}
