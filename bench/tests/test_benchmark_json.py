"""BENCHMARK.json and the files it names agree."""

import importlib
import json
import pathlib

from bench import harness, reference, traffic

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_cell_resolves():
    for w in BENCH["workloads"]:
        spec = harness.cell_spec(w["name"], BENCH)
        assert spec["cell"]["limits"]
        assert reference.machine(spec["config"]).n_links > 0
        assert set(m["name"] for m in spec["end_to_end"]) \
            == {"phase_s", "setup_s"}


def test_metric_readers_state_their_layer():
    for m in BENCH["per_layer"]:
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        assert (mod.LAYER, mod.MOVES) == (m["layer"], m["moves"])


def test_configs_match_their_topology_spec():
    from repro.dragonfly import make_topology
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        topo = make_topology(cfg["topology"])
        mach = reference.machine(cfg)
        assert (topo.n_links, topo.n_nodes) == (mach.n_links, mach.n_nodes)


def test_mixes_are_data_read_by_their_kind():
    for w in BENCH["workloads"]:
        mix = traffic.load_json("traffic", w["traffic"])
        assert traffic.kind_of(mix).draw


def test_reduced_keys_are_the_config_files():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert all(k in cfg["machine"] for k in cfg["reduced"])
