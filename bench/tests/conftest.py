"""Tiny cells for the benchmark's CPU tests: the real configurations and
mixes cut to a few dozen nodes, with the real cells' limits."""

import copy

import pytest

from bench import traffic

SMALL = {
    "aries": ("aries:n_groups=4,chassis_per_group=2,blades_per_chassis=4,"
              "nodes_per_blade=2,global_links_per_pair=2",
              dict(n_groups=4, chassis_per_group=2, blades_per_chassis=4,
                   nodes_per_blade=2, global_links_per_pair=2)),
    "dragonfly": ("dragonfly:p=2,a=4,h=2,g=9", dict(p=2, a=4, h=2, g=9)),
}


def small_config(name: str) -> dict:
    cfg = copy.deepcopy(traffic.load_json("configs", name))
    spec, sizes = SMALL[cfg["machine"]["family"]]
    cfg["topology"] = spec
    cfg["machine"].update(sizes)
    return cfg


def small_mix(name: str) -> dict:
    mix = copy.deepcopy(traffic.load_json("traffic", name))
    if mix["kind"] == "m2m_plan":
        mix.update(n_flows=200)
    else:
        mix.update(ranks=27, groups=2, pattern_args={"nx": 48})
    mix.update(warmup_units=2 if mix["kind"] == "m2m_plan" else 3,
               check_share=0.5, trace_phases=4)
    return mix


def small_spec(cell: str, config: str, mix: str) -> dict:
    limits = traffic.load_json("cells", cell)["limits"]
    return {"name": cell, "chips": 1, "config": small_config(config),
            "mix": small_mix(mix), "cell": {"plan_pairs": None,
                                            "limits": limits},
            "end_to_end": [{"name": "phase_s", "unit": "s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}


@pytest.fixture
def m2m_spec():
    return small_spec("aries12.m2m120k_plan", "aries12", "m2m120k_plan")


@pytest.fixture
def protocol_spec():
    return small_spec("aries12.halo3d512_protocol", "aries12",
                      "halo3d512_protocol")
