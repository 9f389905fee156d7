"""The all-to-all cell on the balanced Dragonfly: its pattern, its band,
a small run of it, and the readers of the policy's and the head's new
spans and counters."""

import importlib
import time

import numpy as np
import pytest

from bench import check, harness, reference, traffic
from bench.kinds import protocol
from bench.patterns import alltoall
from bench.tests.conftest import small_config
from repro.dragonfly import jax_backend

CELL = "dfly_p8a16h8.a2a256_protocol"
MIX = "a2a256_protocol"


@pytest.mark.parametrize("n_ranks", [2, 5, 16, 256])
def test_the_pattern_is_the_programs_alltoall(n_ranks):
    from repro.dragonfly.traffic import alltoall as program
    (want,) = program(n_ranks, 131_072)
    (got,) = alltoall.phases(n_ranks, 131_072)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)
        assert g.dtype == w.dtype
    assert len(got[0]) == n_ranks * (n_ranks - 1) and alltoall.ALLTOALL


def test_the_band_holds_300_seeds_of_reference_plans():
    """Each seed places the job anew; the reference's plan of it has its
    pairs in the cell's one band (seeds small, near 2**31 and above)."""
    cfg = traffic.load_json("configs", "dfly_p8a16h8")
    mix = traffic.load_json("traffic", MIX)
    lo, hi = traffic.load_json("cells", CELL)["plan_pairs"]
    mach = reference.machine(cfg)
    pairs = []
    for seed in [*range(100), *range(2**31 - 50, 2**31 + 50),
                 *range(3_150_000_000, 3_150_000_100)]:
        (src, dst, _), = protocol.draw(mix, mach, cfg["sim"], seed, None)[0]
        stream = reference.Stream(mach, cfg["sim"], seed)
        pairs.append(int(traffic.pairs_per_flow(
            stream.candidates(src, dst)).sum()))
    assert lo <= min(pairs) and max(pairs) <= hi, (min(pairs), max(pairs))
    assert jax_backend._head_len(lo) == jax_backend._head_len(hi) == hi


def _small_spec():
    mix = traffic.load_json("traffic", MIX)
    mix.update(ranks=16, groups=2, warmup_units=3, check_share=0.5,
               trace_phases=6)
    return {"name": CELL, "chips": 1, "config": small_config("dfly_p8a16h8"),
            "mix": mix, "cell": {"plan_pairs": None,
                                 "limits": traffic.load_json(
                                     "cells", CELL)["limits"]},
            "end_to_end": [{"name": "phase_s", "unit": "s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}


def test_a_small_run_is_correct_and_the_control_is_not():
    spec = _small_spec()
    out = harness.run_cell(spec, 5, 0.5, False, t_start=time.perf_counter(),
                           require_tpu=False)
    assert out["correct"], out["check"]
    assert out["check"]["mode_mismatches"]["value"] == 0

    cfg, mix = spec["config"], spec["mix"]
    params = dict(cfg["sim"], backend="numpy", pallas_kernel="auto")
    d = traffic.Driver(mix, cfg, 3, None, params, lambda i: True)
    while d.phases_run < 9:
        d.step()
    assert {m for _, m, _ in d.recorder.decided} <= {"ADAPTIVE_1",
                                                     "ADAPTIVE_3"}
    r = check.compare(d, control=True)
    limits = spec["cell"]["limits"]
    assert check.verdict(r["program"], limits)
    assert not check.verdict(r["control"], limits), r["control"]


# ------------------------------------------------------------- the readers
def reader(name):
    return importlib.import_module(f"bench.metrics.{name}").read


def obs(phases=4, **stages):
    return {"phases": phases, "stages_s": dict(stages), "trace": None}


@pytest.mark.parametrize("name,stage", [("decide_s", "decide"),
                                        ("publish_s", "publish")])
def test_policy_span_readers(name, stage):
    assert reader(name)(obs(4, **{stage: 2.0, "policy": 3.0})) \
        == pytest.approx(0.5)
    assert reader(name)(obs(4, policy=3.0)) is None
    assert reader(name)(obs(0, **{stage: 2.0})) is None


@pytest.fixture
def segsum(monkeypatch):
    monkeypatch.setattr(jax_backend, "SEGSUM",
                        {"sorted_calls": 40, "dense_calls": 48,
                         "grid_steps": 204_224, "head_pairs": 8 * 1_272_327,
                         "head_pad_pairs": 8 * (1_310_720 - 1_272_327)},
                        raising=False)
    return monkeypatch


def test_head_pad_share_is_the_pad_over_the_real_pairs(segsum):
    assert reader("head_pad_share")(obs()) \
        == pytest.approx(100 * 38_393 / 1_272_327)


def test_head_pad_share_without_its_counter_or_phases_is_none(segsum):
    assert reader("head_pad_share")(obs(0)) is None
    segsum.setattr(jax_backend, "SEGSUM", {"grid_steps": 9})
    assert reader("head_pad_share")(obs()) is None
    segsum.setattr(jax_backend, "SEGSUM", {"head_pairs": 0,
                                           "head_pad_pairs": 0})
    assert reader("head_pad_share")(obs()) is None
    segsum.delattr(jax_backend, "SEGSUM")
    assert reader("head_pad_share")(obs()) is None
