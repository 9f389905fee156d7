"""The plain reference against the program's float64 numpy backend,
which replays the same stream draw for draw: they agree to rounding."""

import numpy as np
import pytest

from bench import check, reference, traffic
from bench.tests.conftest import small_config, small_mix


def _driver(config, mix_name, backend, phases, seed=11, band=None):
    cfg, mix = small_config(config), small_mix(mix_name)
    params = dict(cfg["sim"], backend=backend, pallas_kernel="auto")
    d = traffic.Driver(mix, cfg, seed, band, params, lambda i: True)
    while d.phases_run < phases:
        d.step()
    return d


@pytest.mark.parametrize("config", ["aries12", "dfly_p8a16h8"])
def test_candidates_match_the_program(config):
    from repro.dragonfly import make_topology
    cfg = small_config(config)
    topo, mach = make_topology(cfg["topology"]), reference.machine(cfg)
    assert topo.n_links == mach.n_links
    assert mach.n_real_links == int((topo.link_endpoints()[1] != -1).sum())
    np.testing.assert_array_equal(topo.capacity_gbs, mach.capacity_gbs)
    rng = np.random.default_rng(3)
    src = rng.integers(0, mach.n_nodes, 3000)
    dst = rng.integers(0, mach.n_nodes, 3000)
    dst[:40] = src[:40]
    a, _ = topo.candidate_paths(src, dst, np.random.default_rng(9),
                                n_min=4, n_nonmin=2)
    ch = mach.choices(len(src), np.random.default_rng(9), 4, 2)
    np.testing.assert_array_equal(a, mach.paths(src, dst, ch, 4, 2))
    # a flow's paths rebuilt alone from its own choices
    idx = np.arange(5, 3000, 7)
    b = mach.paths(src[idx], dst[idx], {k: v[..., idx] for k, v in ch.items()},
                   4, 2)
    np.testing.assert_array_equal(a[idx], b)


@pytest.mark.parametrize("config,mix,band", [
    ("aries12", "m2m120k_plan", None),
    ("aries12", "m2m120k_plan", [4380, 4400]),     # endpoints refitted
    ("dfly_p8a16h8", "m2m120k_plan", None),
    ("dfly_p8a16h8", "m2m120k_plan", [3550, 3570]),
    ("aries12", "halo3d512_protocol", None)])
def test_reference_replays_the_numpy_backend(config, mix, band):
    d = _driver(config, mix, "numpy", 12, band=band)
    if band is not None:
        assert band[0] <= d.plans[0].pair_links.shape[0] <= band[1]
    r = check.compare(d)
    for name in ("t_rel_err", "lat_rel_err", "stall_abs_err",
                 "queue_rel_err"):
        assert r["program"][name] < 1e-9, (name, r["program"])
    assert r["program"].get("mode_mismatches", 0) == 0
    assert r["phases"] == 12


def test_algorithm1_gate_and_switch():
    cfg = {"mode_a": "A", "mode_b": "B", "mode_a_alltoall": "A1",
           "cumulative_threshold_bytes": 4096, "max_sample_age": 16,
           "lambda_latency": 0.8, "sigma_stalls": 1.6}
    alg = reference.Algorithm1(cfg)
    assert alg.decide(1024) == "B"            # below the gate
    assert alg.decide(32768) == "A"           # nothing observed: default
    alg.observe("A", 5000.0, 0.0)             # high latency, no stalls
    assert alg.decide(32768) == "B"           # lambda*L wins
    assert reference.flits_packets(32768) == (2560, 512)


@pytest.mark.parametrize("mix", ["m2m120k_plan", "halo3d512_protocol"])
def test_a_free_running_replay_of_the_numpy_backend(mix):
    """The float64 program needs no re-sync: the reference carrying its
    own state and feeding Algorithm 1 its own (L, s) still agrees."""
    d = _driver("aries12", mix, "numpy", 12)
    r = check.compare(d, free=True)
    assert max(r["by_phase"]) < 1e-9 and len(r["by_phase"]) == 12
    assert r["program"]["queue_rel_err"] < 1e-9
    assert r["program"].get("mode_mismatches", 0) == 0


def test_a_free_running_replay_needs_every_phase():
    cfg, m = small_config("aries12"), small_mix("m2m120k_plan")
    params = dict(cfg["sim"], backend="numpy", pallas_kernel="auto")
    d = traffic.Driver(m, cfg, 11, None, params, lambda i: i % 2 == 0)
    for _ in range(4):
        d.step()
    with pytest.raises(ValueError, match="every phase"):
        check.compare(d, free=True)
