"""``correct`` comes out false for the control and for a broken timed
path, and true for a sound run, with the real cells' limits."""

import time

import numpy as np
import pytest

from bench import check, harness, traffic
from bench.tests.conftest import small_config, small_mix


def _run(spec, seed=5):
    return harness.run_cell(spec, seed, 0.5, False,
                            t_start=time.perf_counter(), require_tpu=False)


def test_a_sound_run_is_correct(m2m_spec, protocol_spec):
    for spec in (m2m_spec, protocol_spec):
        out = _run(spec)
        assert out["correct"], out["check"]
        assert list(out)[-1] == "check"
        assert set(out["metrics"]) == {"phase_s", "setup_s"}


@pytest.mark.parametrize("mix", ["m2m120k_plan", "halo3d512_protocol"])
def test_the_bfloat16_control_is_not_correct(mix):
    cfg = small_config("aries12")
    m = small_mix(mix)
    params = dict(cfg["sim"], backend="numpy", pallas_kernel="auto")
    d = traffic.Driver(m, cfg, 3, None, params, lambda i: True)
    while d.phases_run < 8:
        d.step()
    cell = "aries12.m2m120k_plan" if mix == "m2m120k_plan" \
        else "aries12.halo3d512_protocol"
    limits = traffic.load_json("cells", cell)["limits"]
    r = check.compare(d, control=True)
    assert check.verdict(r["program"], limits)
    assert not check.verdict(r["control"], limits), r["control"]


def _state_unchanged(monkeypatch):
    from repro.dragonfly.simulator import DragonflySimulator
    orig = DragonflySimulator._phase_finish

    def finish(self, ctx, out):
        lq, mem = self.link_queue_s.copy(), self.est_memory_s.copy()
        res = orig(self, ctx, out)
        self.link_queue_s, self.est_memory_s = lq, mem
        return res
    monkeypatch.setattr(DragonflySimulator, "_phase_finish", finish)


def _half_the_flows(monkeypatch):
    from repro.dragonfly import jax_backend
    orig = jax_backend._prepare_inputs

    def prepare(sim, ctx):
        inputs, statics = orig(sim, ctx)
        size_all = np.asarray(inputs[14]).copy()
        size_all[ctx["n_app"] // 2:ctx["n_app"]] = 0.0
        inputs = inputs[:14] + (jax_backend._f32(size_all),) + inputs[15:]
        return inputs, statics
    monkeypatch.setattr(jax_backend, "_prepare_inputs", prepare)


def _one_answer_altered(monkeypatch):
    from repro.dragonfly.simulator import DragonflySimulator
    orig = DragonflySimulator._phase_finish

    def finish(self, ctx, out):
        res = orig(self, ctx, out)
        res.t_us[0] *= 1.01
        return res
    monkeypatch.setattr(DragonflySimulator, "_phase_finish", finish)


@pytest.mark.parametrize("fault,cell", [
    (_half_the_flows, "m2m"), (_one_answer_altered, "m2m"),
    (_state_unchanged, "protocol"), (_half_the_flows, "protocol"),
    (_one_answer_altered, "protocol")])
def test_a_broken_timed_path_is_not_correct(fault, cell, m2m_spec,
                                            protocol_spec, monkeypatch):
    # the 120k-flow phases carry no queue into the next (each phase is
    # longer than any link's backlog), so a state left unchanged is no
    # fault those cells can have; the protocol cell carries queues
    fault(monkeypatch)
    out = _run(m2m_spec if cell == "m2m" else protocol_spec)
    assert not out["correct"], out["check"]


def test_a_traced_run_reports_its_layers(m2m_spec, monkeypatch):
    """The traced path end to end on the CPU, where no TPU plane exists:
    the profiler runs and its file is read, the reduction is a stand-in."""
    from bench import trace
    seen = {}

    def reduce(events, patterns):
        seen["events"], seen["patterns"] = len(events), patterns
        return {"window_s": 1.0, "busy_s": 0.75, "devices": 1,
                "kernel_s": {"segsum_s": 0.5}, "device_ops": [["op", 0.5]],
                "idle_gaps": [["bench.phase_begin", 0.25]]}
    monkeypatch.setattr(trace, "reduce", reduce)
    monkeypatch.setattr(harness, "peak_of",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    m2m_spec["per_layer"] = [
        {"name": n, "unit": u} for n, u in (
            ("host_prep_s", "s"), ("fixed_point_s", "s"), ("segsum_s", "s"),
            ("segsum_roofline", "%"), ("device_idle", "%"))]
    out = harness.run_cell(m2m_spec, 5, 30.0, True,
                           t_start=time.perf_counter(), require_tpu=False)
    assert seen["events"] > 0
    assert seen["patterns"] == {"segsum_s": r"^segment_sum"}
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"host_prep_s", "fixed_point_s",
                                   "segsum_s", "segsum_roofline",
                                   "device_idle"}
    assert out["attempted"] == m2m_spec["mix"]["trace_phases"]
    assert out["metrics"]["device_idle"]["value"] == pytest.approx(25.0)
    assert out["device"]["busy_s"] == 0.75
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "check"]
