"""One many-to-many phase of ``n_flows`` uniform random flows (src !=
dst) with Pareto-distributed sizes, replayed phase after phase through
the simulator's plan cache under one routing ``mode``, with no
allocation (the background flows may land anywhere)."""

from __future__ import annotations

import numpy as np

from bench import reference, traffic

HOST_DRAWS = False
#: rounds of single-flow redraws before ``fit_pairs`` gives up
MAX_ROUNDS = 10_000


def flows(n_nodes: int, n_flows: int, rng, size: dict):
    """Uniform random src != dst with Pareto sizes (bytes)."""
    src = rng.integers(0, n_nodes, size=n_flows)
    dst = (src + rng.integers(1, n_nodes, size=n_flows)) % n_nodes
    nbytes = rng.pareto(size["pareto_alpha"], size=n_flows) \
        * size["scale_bytes"] + size["floor_bytes"]
    return src, dst, nbytes


def fit_pairs(stream, phase, ch: dict, band, rng, batch: int = 256):
    """``phase`` with single flows' endpoints redrawn (uniform, src !=
    dst; sizes and the stream's choices stay at their index) until the
    plan's pair count lies in ``band``: each redraw is kept when it
    brings the count nearer the band's middle.  Some hundred of 120,000
    flows move."""
    src, dst, nbytes = (np.array(a) for a in phase)
    n, n_nodes = len(src), stream.m.n_nodes
    per = traffic.pairs_per_flow(stream.paths(src, dst, ch))
    total, mid = int(per.sum()), (band[0] + band[1]) // 2
    for _ in range(MAX_ROUNDS):
        if band[0] <= total <= band[1]:
            return src, dst, nbytes
        idx = rng.choice(n, size=min(batch, n), replace=False)
        s = rng.integers(0, n_nodes, size=idx.size)
        d = (s + rng.integers(1, n_nodes, size=idx.size)) % n_nodes
        new = traffic.pairs_per_flow(stream.paths(
            s, d, {k: v[..., idx] for k, v in ch.items()}))
        for j, i in enumerate(idx):
            delta = int(new[j] - per[i])
            if abs(mid - total - delta) < abs(mid - total):
                src[i], dst[i], per[i] = s[j], d[j], new[j]
                total += delta
                if band[0] <= total <= band[1]:
                    break
    raise RuntimeError(f"{total} plan pairs after {MAX_ROUNDS} rounds, "
                       f"outside the band {band}")


def draw(mix: dict, mach, sim: dict, seed: int, band):
    rng = np.random.default_rng([seed, 0])
    phase = flows(mach.n_nodes, mix["n_flows"], rng, mix["size"])
    if band is not None:
        stream = reference.Stream(mach, sim, seed)
        phase = fit_pairs(stream, phase, stream.choices(mix["n_flows"]),
                          band, rng)
    return [phase], None


class Loop:
    def __init__(self, driver):
        self.driver = driver
        self.policy = driver.policy(driver.mix["mode"])

    def step(self) -> int:
        d = self.driver
        (s, t, b), = d.phases
        d.sim.run_phase(s, t, b, self.policy, plan=d.plans[0])
        return 1


def extra_numbers(driver, observed) -> dict:
    return {}
