import numpy as np
import pytest

from bench import reference, traffic
from bench.kinds import m2m_plan, protocol
from bench.patterns import halo3d
from bench.tests.conftest import small_config, small_mix


def test_m2m_flows_are_seeded_and_never_self():
    size = {"pareto_alpha": 1.2, "scale_bytes": 65536, "floor_bytes": 1024}
    a = m2m_plan.flows(64, 500, np.random.default_rng([7, 0]), size)
    b = m2m_plan.flows(64, 500, np.random.default_rng([7, 0]), size)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    src, dst, nbytes = a
    assert (src != dst).all() and (nbytes >= 1024).all()
    assert src.max() < 64 and dst.max() < 64


def test_halo3d_faces():
    phases = halo3d.phases(27, 48)            # 3x3x3 ranks, 16^3 each
    assert len(phases) == 6
    for src, dst, nbytes in phases:
        assert len(src) == 18                 # 27 - one 3x3 face
        assert (nbytes == 16 * 16 * 8).all()
        assert (src != dst).all()
    assert halo3d.grid_dims(512, 3) == [8, 8, 8]


def test_group_placement_packs_k_groups():
    mach = reference.machine(small_config("aries12"))
    nodes = protocol.placement(mach, 20, 2, np.random.default_rng([3, 0]))
    assert len(set(nodes.tolist())) == 20
    assert len(set((nodes // mach.nodes_per_group).tolist())) == 2


def _pairs(mach, sim, seed, phases):
    stream = reference.Stream(mach, sim, seed)
    return [int(traffic.pairs_per_flow(stream.candidates(s, d)).sum())
            for s, d, _ in phases]


@pytest.mark.parametrize("config", ["aries12", "dfly_p8a16h8"])
def test_draws_land_in_the_band(config):
    cfg, mix = small_config(config), small_mix("m2m120k_plan")
    mach, sim = reference.machine(cfg), cfg["sim"]
    free, _ = m2m_plan.draw(mix, mach, sim, 5, None)
    (n,) = _pairs(mach, sim, 5, free)
    band = [n - 60, n - 40]
    for seed in (5, 6, 7):
        phases, nodes = m2m_plan.draw(mix, mach, sim, seed, band)
        assert nodes is None
        assert band[0] <= _pairs(mach, sim, seed, phases)[0] <= band[1]
        src, dst, nbytes = phases[0]
        raw = m2m_plan.flows(mach.n_nodes, mix["n_flows"],
                             np.random.default_rng([seed, 0]), mix["size"])
        moved = (src != raw[0]) | (dst != raw[1])
        assert (src != dst).all() and moved.sum() < len(src) // 4
        np.testing.assert_array_equal(nbytes, raw[2])
        assert moved.any() or seed != 5       # seed 5 reads n freely


def test_a_job_outside_the_band_is_an_error():
    cfg, mix = small_config("aries12"), small_mix("halo3d512_protocol")
    mach, sim = reference.machine(cfg), cfg["sim"]
    phases, nodes = protocol.draw(mix, mach, sim, 5, None)
    assert len(nodes) == mix["ranks"]
    pairs = _pairs(mach, sim, 5, phases)
    protocol.draw(mix, mach, sim, 5, [min(pairs), max(pairs)])
    with pytest.raises(RuntimeError, match="outside the band"):
        protocol.draw(mix, mach, sim, 5, [max(pairs) + 1, max(pairs) + 9])


def test_the_kinds_are_found_by_name():
    for name in ("m2m120k_plan", "halo3d512_protocol"):
        kind = traffic.kind_of(traffic.load_json("traffic", name))
        assert callable(kind.draw) and hasattr(kind.Loop, "step")
        assert isinstance(kind.HOST_DRAWS, bool)
    assert traffic.pattern_of("halo3d") is halo3d
