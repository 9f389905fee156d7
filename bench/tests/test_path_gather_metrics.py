"""The readers of the Pallas path gather's device time and of the
program's gather-slot counter, on hand-made observations."""

import importlib

import pytest

from repro.dragonfly import jax_backend


def read(name, obs):
    return importlib.import_module(f"bench.metrics.{name}").read(obs)


def obs(phases=4, kernel_s=None):
    trace = None if kernel_s is None else {"kernel_s": kernel_s}
    return {"phases": phases, "stages_s": {}, "trace": trace}


def test_path_gather_s_is_device_seconds_per_phase():
    assert read("path_gather_s", obs(4, {"path_gather_s": 0.2,
                                         "segsum_s": 9.0})) \
        == pytest.approx(0.05)


@pytest.mark.parametrize("o", [
    obs(4), obs(4, {"segsum_s": 9.0}), obs(4, {"path_gather_s": 0.0}),
    obs(0, {"path_gather_s": 0.2})],
    ids=["untraced", "no_kernel_in_the_trace", "zero", "no_phase"])
def test_path_gather_s_without_its_kernel_is_none(o):
    assert read("path_gather_s", o) is None


@pytest.mark.parametrize("counts,share", [
    ({"kernel_slots": 600, "xla_slots": 0}, 100.0),
    ({"kernel_slots": 150, "xla_slots": 450}, 25.0),
    ({"kernel_slots": 0, "xla_slots": 600}, 0.0)],
    ids=["all", "a_quarter", "none"])
def test_path_gather_share_is_the_kernels_slots(monkeypatch, counts, share):
    monkeypatch.setattr(jax_backend, "GATHER", counts, raising=False)
    assert read("path_gather_share", obs()) == pytest.approx(share)


def test_path_gather_share_without_its_counter_is_none(monkeypatch):
    monkeypatch.setattr(jax_backend, "GATHER",
                        {"kernel_slots": 0, "xla_slots": 0}, raising=False)
    assert read("path_gather_share", obs()) is None
    monkeypatch.setattr(jax_backend, "GATHER", {})
    assert read("path_gather_share", obs()) is None
    monkeypatch.delattr(jax_backend, "GATHER")
    assert read("path_gather_share", obs()) is None


def test_the_path_gathers_pattern_leaves_the_segment_sum_alone():
    import re
    gather = importlib.import_module("bench.metrics.path_gather_s").PATTERN
    segsum = importlib.import_module("bench.metrics.segsum_s").PATTERN
    assert re.search(gather, "path_gather_pallas.3")
    assert not re.search(segsum, "path_gather_pallas.3")
    assert not re.search(gather, "segment_sum_pallas.13")
