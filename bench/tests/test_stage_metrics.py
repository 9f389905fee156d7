"""The readers of the program's stage spans and transfer counters, on
hand-made observations."""

import importlib

import pytest

from repro.dragonfly import jax_backend

SPANS = {"transfer_s": "transfer", "device_wait_s": "device_wait",
         "fetch_s": "fetch", "policy_s": "policy"}
COUNTERS = ("h2d_copies", "h2d_bytes", "d2h_bytes")


def reader(name):
    return importlib.import_module(f"bench.metrics.{name}").read


def obs(phases=4, **stages):
    return {"phases": phases, "stages_s": dict(stages), "trace": None}


@pytest.mark.parametrize("name", sorted(SPANS))
def test_span_reader_is_seconds_per_phase(name):
    assert reader(name)(obs(4, **{SPANS[name]: 2.0, "fixed_point": 9.0})) \
        == pytest.approx(0.5)


@pytest.mark.parametrize("name", sorted(SPANS))
def test_span_reader_without_its_stage_is_none(name):
    assert reader(name)(obs(4, fixed_point=9.0)) is None
    assert reader(name)(obs(0, **{SPANS[name]: 2.0})) is None


@pytest.fixture
def counters(monkeypatch):
    monkeypatch.setattr(jax_backend, "PIPELINE_CALLS",
                        {"single": 6, "batched": 2})
    monkeypatch.setattr(jax_backend, "TRANSFER",
                        {"h2d_copies": 216, "h2d_bytes": 8000,
                         "d2h_bytes": 400})
    return monkeypatch


@pytest.mark.parametrize("name", COUNTERS)
def test_counter_reader_is_the_mean_per_dispatch(counters, name):
    expect = {"h2d_copies": 27, "h2d_bytes": 1000, "d2h_bytes": 50}[name]
    assert reader(name)(obs()) == pytest.approx(expect)


@pytest.mark.parametrize("name", COUNTERS)
def test_counter_reader_without_its_counter_is_none(counters, name):
    counters.setattr(jax_backend, "TRANSFER", {})
    assert reader(name)(obs()) is None
    counters.delattr(jax_backend, "TRANSFER")
    assert reader(name)(obs()) is None


@pytest.mark.parametrize("name", COUNTERS)
def test_counter_reader_without_dispatches_is_none(counters, name):
    counters.setattr(jax_backend, "PIPELINE_CALLS",
                     {"single": 0, "batched": 0})
    assert reader(name)(obs()) is None
