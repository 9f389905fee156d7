"""The reader of the program's segment-sum grid-step counter, on
hand-made observations."""

import importlib

import pytest

from repro.dragonfly import jax_backend


def read(obs):
    return importlib.import_module("bench.metrics.segsum_steps").read(obs)


def obs(phases=4):
    return {"phases": phases, "stages_s": {}, "trace": None}


@pytest.fixture
def segsum(monkeypatch):
    monkeypatch.setattr(jax_backend, "PIPELINE_CALLS",
                        {"single": 6, "batched": 2})
    monkeypatch.setattr(jax_backend, "SEGSUM",
                        {"sorted_calls": 40, "dense_calls": 48,
                         "grid_steps": 204_224}, raising=False)
    return monkeypatch


def test_segsum_steps_is_the_mean_per_dispatch(segsum):
    assert read(obs()) == pytest.approx(25_528)


def test_segsum_steps_without_its_counter_is_none(segsum):
    segsum.setattr(jax_backend, "SEGSUM", {})
    assert read(obs()) is None
    segsum.delattr(jax_backend, "SEGSUM")
    assert read(obs()) is None


def test_segsum_steps_without_dispatches_is_none(segsum):
    segsum.setattr(jax_backend, "PIPELINE_CALLS",
                   {"single": 0, "batched": 0})
    assert read(obs()) is None
