"""Plain numpy reference of one simulated phase, independent of the program.

It implements, from the configuration's numbers alone, what the
simulator's documentation and the paper define:

  * the machine: link numbering, capacities, NIC links, and the
    minimal and Valiant candidate paths per flow, one file per family
    under ``bench/machines/``;
  * the simulator's random stream, draw for draw: the background flows
    of other jobs, the candidate draws, the phantom-congestion noise and
    the per-packet spray noise, all from ``numpy.random.default_rng(seed)``
    in the order the simulator documents;
  * one phase of the fluid model: noisy scores, softmin spray, the
    feedback fixed point, per-link loads, latency ``L`` and stalls ``s``,
    the paper's Eq. (2) message time, and the queue state carried into
    the next phase;
  * Algorithm 1 of the paper (application-aware selection) at one call
    site, one decision per phase.

Everything is float64 and written for clarity, not speed: gathers over
the whole candidate tensor and ``np.bincount`` sums over every valid
hop.  ``precision="bfloat16"`` rounds every intermediate array to
bfloat16, which is the control of the correctness check.  It imports
nothing of the program.
"""

from __future__ import annotations

import importlib
import math
from dataclasses import dataclass

import numpy as np

PAD = -1
MAX_HOPS = 8
MAX_OUTSTANDING_PACKETS = 1024


# ------------------------------------------------------------------ machines
def machine(config: dict):
    """The configuration's machine, by its family: ``bench/machines/
    <family>.py``."""
    m = config["machine"]
    return importlib.import_module(f"bench.machines.{m['family']}") \
        .Machine(m)


# --------------------------------------------------------------- the stream
@dataclass
class Draws:
    """Everything one phase draws from the simulator's stream."""

    bg_src: np.ndarray
    bg_dst: np.ndarray
    bg_size: np.ndarray
    bg_links: np.ndarray
    noise: np.ndarray           # [n_links] lognormal phantom factor
    ghosts: np.ndarray          # [n_links] exponential phantom ghosts
    gumbel: np.ndarray          # [iters, n_rows, ncand] spray noise


class Stream:
    """The simulator's random stream, replayed from its seed."""

    def __init__(self, mach, sim: dict, seed: int):
        self.m, self.sim = mach, sim
        self.rng = np.random.default_rng(seed)
        self.n_cand = sim["n_min_candidates"] + sim["n_nonmin_candidates"]
        self.hot = self.rng.choice(
            mach.n_groups, size=min(sim["bg_hot_groups"], mach.n_groups),
            replace=False)
        self.phases = 0

    def choices(self, n: int) -> dict:
        return self.m.choices(n, self.rng, self.sim["n_min_candidates"],
                              self.sim["n_nonmin_candidates"])

    def paths(self, src, dst, ch: dict):
        return self.m.paths(src, dst, ch, self.sim["n_min_candidates"],
                            self.sim["n_nonmin_candidates"])

    def candidates(self, src, dst):
        return self.paths(src, dst, self.choices(len(src)))

    def _bg_nodes(self, n, ours):
        m, s, rng = self.m, self.sim, self.rng
        hot = rng.random(n) < s["bg_hot_prob"]
        grp = np.where(hot, rng.choice(self.hot, size=n),
                       rng.integers(0, m.n_groups, size=n))
        out = grp * m.nodes_per_group \
            + rng.integers(0, m.nodes_per_group, size=n)
        # other jobs never share the allocation's nodes: redraw
        for _ in range(3):
            bad = np.isin(out, ours)
            if not bad.any():
                return out
            out[bad] = rng.integers(0, m.n_nodes, size=bad.sum())
        bad = np.isin(out, ours)
        if bad.any():
            free = np.setdiff1d(np.arange(m.n_nodes), ours)
            if free.size:
                out[bad] = rng.choice(free, size=bad.sum())
        return out

    def phase(self, n_app: int, ours) -> Draws:
        """Draws of one phase whose app flows come from a plan."""
        m, s, rng = self.m, self.sim, self.rng
        n_bg = s["bg_flows_per_phase"]
        self.phases += 1
        if self.phases % max(1, s["bg_rotate_phases"]) == 0:
            self.hot = rng.choice(m.n_groups,
                                  size=min(s["bg_hot_groups"], m.n_groups),
                                  replace=False)
        ours = np.asarray(ours, dtype=np.int64)
        src = self._bg_nodes(n_bg, ours)
        dst = self._bg_nodes(n_bg, ours)
        dst = np.where(dst == src, (dst + 1) % m.n_nodes, dst)
        for _ in range(m.n_nodes):
            bad = np.isin(dst, ours) | (dst == src)
            if not bad.any():
                break
            dst = np.where(bad, (dst + 1) % m.n_nodes, dst)
        size = (rng.pareto(s["bg_pareto_alpha"], size=n_bg) + 1.0) \
            * s["bg_bytes_scale"]
        links = self.candidates(src, dst)
        noise = rng.lognormal(0.0, s["phantom_sigma"], size=m.n_links)
        ghosts = rng.exponential(s["phantom_ghost_s"], size=m.n_links)
        gumbel = rng.gumbel(0.0, 1.0, size=(max(1, s["route_feedback_iters"]),
                                            n_app + n_bg, self.n_cand))
        return Draws(src, dst, size, links, noise, ghosts, gumbel)

    def host_noise(self):
        """The host-overhead draw the paper's iteration loop makes."""
        return self.rng.lognormal(0.0, self.sim["host_noise_sigma"])


# ---------------------------------------------------------------- one phase
def _bf16(x):
    import ml_dtypes
    return np.asarray(x, dtype=np.float64).astype(ml_dtypes.bfloat16) \
        .astype(np.float64)


def _f64(x):
    return np.asarray(x, dtype=np.float64)


def mode_bias_s(mode: str, routing: dict) -> float:
    b = routing["minimal_bias"][mode]
    if mode == "ADAPTIVE_1":                # ramps along the path: half
        return b * 0.5 * routing["bias_unit_s"]
    return b if math.isinf(b) else b * routing["bias_unit_s"]


def run_phase(mach, sim: dict, routing: dict, app_links, app_size,
              app_src, modes, draws: Draws, link_queue_s, est_memory_s,
              precision: str = "float64") -> dict:
    """One phase of the fluid model from its inputs and carried state.

    ``modes``: [n_app] mode names (background flows are ADAPTIVE_0).
    Returns the app flows' ``t_us``, ``latency_us``, ``stalls_per_flit``
    and the carried ``link_queue_s`` / ``est_memory_s`` after the phase."""
    q = _bf16 if precision == "bfloat16" else _f64
    n_app = app_links.shape[0]
    links = np.concatenate([app_links, draws.bg_links])
    valid = links != PAD
    safe = np.where(valid, links, 0)
    size = np.concatenate([np.asarray(app_size, float), draws.bg_size])
    src = np.concatenate([np.asarray(app_src, np.int64), draws.bg_src])
    n, ncand, _ = links.shape
    is_nonmin = np.arange(ncand) >= sim["n_min_candidates"]
    hops = valid.sum(axis=-1)
    nic = mach.nic_link(src)
    cap_bps = mach.capacity_gbs * 1e9
    lq = _f64(link_queue_s)
    mem = _f64(est_memory_s)
    a = sim["est_staleness"]
    est = q(((1.0 - a) * lq + a * mem) * draws.noise + draws.ghosts)
    ser = float(np.max(app_size)) * sim["flit_ns_per_byte"] * 1e-9 \
        if n_app else 0.0
    window = max(ser, sim["min_phase_window_s"])
    size_inst = np.minimum(size, cap_bps[nic] * window)
    cap_window = cap_bps * window

    bias = np.zeros(n)
    pos = np.zeros(n, dtype=bool)
    neg = np.zeros(n, dtype=bool)
    for i, mode in enumerate(list(modes) + ["ADAPTIVE_0"] * (n - n_app)):
        b = mode_bias_s(mode, routing)
        pos[i], neg[i] = b == math.inf, b == -math.inf
        bias[i] = 0.0 if math.isinf(b) else b
    temp = max(routing["spray_temperature_s"], 1e-12)
    packets = np.maximum(1, np.ceil(size / 64.0))
    noise_scale = (temp * 0.9) / np.sqrt(np.maximum(packets, 1.0))[:, None]

    def score_of(est_link):
        s = (est_link[safe] * valid).sum(axis=-1) \
            + routing["hop_latency_s"] * hops
        s = s + np.where(is_nonmin[None, :], bias[:, None], 0.0)
        s = np.where(pos[:, None] & is_nonmin[None, :], np.inf, s)
        return q(np.where(neg[:, None] & ~is_nonmin[None, :], np.inf, s))

    def spray(score, g):
        s = score + g * noise_scale
        s = np.where(np.isfinite(s), s, np.inf)
        smin = s.min(axis=1, keepdims=True)
        smin = np.where(np.isfinite(smin), smin, 0.0)
        z = np.exp(-(s - smin) / temp)
        tot = z.sum(axis=1, keepdims=True)
        return q(z / np.where(tot <= 0, 1.0, tot))

    def link_sum(per_row, w):
        return np.bincount(safe[valid], minlength=mach.n_links,
                           weights=np.broadcast_to(
                               (per_row[:, None] * w)[:, :, None],
                               links.shape)[valid])

    nic_load = np.bincount(nic, weights=size_inst, minlength=mach.n_links)
    score0 = score_of(est)
    w = spray(score0, draws.gumbel[0])
    load = q(link_sum(size_inst, w) + nic_load)
    for it in range(1, draws.gumbel.shape[0]):
        extra = np.maximum(0.0, load / cap_window - sim["feedback_rho0"]) \
            * window
        score = q(score0 + (extra[safe] * valid).sum(axis=-1))
        w = q(0.5 * (w + spray(score, draws.gumbel[it])))
        load = q(link_sum(size_inst, w) + nic_load)
    load_q = q(link_sum(size, w))
    rho = q(load / cap_window)

    thr = sim["rho_threshold"]
    rho_path = rho[safe] * valid
    qdelay = sim["queue_delay_ns"] * np.maximum(0.0, rho_path - thr).sum(-1)
    qwait = (lq[safe] * valid).sum(axis=-1) * sim["qwait_fraction"] * 1e9
    lat_cand = 2.0 * mach.nic_latency_ns + hops * mach.hop_latency_ns \
        + qdelay + qwait
    lat_us = q((lat_cand * w).sum(axis=-1) / 1e3)
    bneck = np.maximum(rho_path.max(axis=-1), rho[nic][:, None])
    stalls = q((sim["stall_gain"] * np.maximum(0.0, bneck - thr) * w).sum(-1))

    clk = sim["nic_clock_ghz"]
    flits = packets * 5.0
    win = (packets + MAX_OUTSTANDING_PACKETS // 2) / MAX_OUTSTANDING_PACKETS
    t_us = q((win * lat_us * 1e3 * clk + flits * (stalls + 1.0))
             / (1e3 * clk))
    duration = max(float(t_us[:n_app].max()) * 1e-6, 1e-7) if n_app \
        else window
    excess = np.maximum(0.0, load_q / cap_bps - max(duration, window))
    decay = sim["est_memory_decay"]
    return {"t_us": t_us[:n_app], "latency_us": lat_us[:n_app],
            "stalls_per_flit": stalls[:n_app],
            "link_queue_s": q(lq * sim["queue_carryover"] + excess),
            "est_memory_s": q(mem * decay + lq * (1 - decay))}


# ------------------------------------------------------------- Algorithm 1
def flits_packets(size_bytes: int) -> tuple[int, int]:
    """A PUT message: 64-byte packets of 1 header + 4 payload flits."""
    packets = max(1, -(-size_bytes // 64))
    full, rem = divmod(size_bytes, 64)
    return full * 5 + (1 + -(-rem // 16) if rem else 0), packets


def eq2_cycles(lat_cycles, stalls, flits, packets):
    """Eq. (2): T = (p + 512) / 1024 * L + f * (s + 1)."""
    return (packets + MAX_OUTSTANDING_PACKETS // 2) \
        / MAX_OUTSTANDING_PACKETS * lat_cycles + flits * (stalls + 1.0)


class Algorithm1:
    """Application-aware selection at one call site, one step per phase
    (paper §4.2-4.3): the cumulative-size gate, the Eq. (3) choice
    between the default and the high-bias mode on the Eq. (2) model, and
    the lambda/sigma estimate of a mode whose sample is too old."""

    def __init__(self, cfg: dict, alltoall: bool = False):
        self.cfg = cfg
        self.a = cfg["mode_a_alltoall"] if alltoall else cfg["mode_a"]
        self.b = cfg["mode_b"]
        self.current = cfg["mode_a"]
        self.cum = 0
        self.samples: dict = {}       # mode -> [lat_cycles, stalls, age]

    def decide(self, msg_bytes: int) -> str:
        cfg = self.cfg
        self.cum += msg_bytes
        if self.cum < cfg["cumulative_threshold_bytes"]:
            return self.b
        self.cum = 0
        is_b = self.current == self.b
        known = self.b if is_b else (self.current if self.current
                                     in self.samples else self.a)
        if known not in self.samples:
            chosen = self.b if is_b else self.a
        else:
            kl, ks, _ = self.samples[known]
            other = self.a if is_b else self.b
            lam, sig = cfg["lambda_latency"], cfg["sigma_stalls"]
            if is_b:
                lam, sig = 1.0 / max(lam, 1e-9), 1.0 / max(sig, 1e-9)
            stored = self.samples.get(other)
            if stored is not None and stored[2] <= cfg["max_sample_age"]:
                ol, os_ = stored[0], stored[1]
            else:
                ol, os_ = kl * lam, ks * sig
            f, p = flits_packets(msg_bytes)
            t_known = eq2_cycles(kl, ks, f, p)
            t_other = eq2_cycles(ol, os_, f, p)
            t_a, t_b = (t_other, t_known) if is_b else (t_known, t_other)
            chosen = self.b if t_b < t_a else self.a
        self.current = chosen
        return chosen

    def observe(self, mode: str, lat_cycles: float, stalls: float) -> None:
        for s in self.samples.values():
            s[2] += 1
        self.samples[mode] = [lat_cycles, stalls, 0]
