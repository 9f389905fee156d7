import gzip
import json
import pathlib

import pytest

from bench import trace

DATA = pathlib.Path(__file__).parent / "data"
DEV, HOST = "/device:TPU:0", "/host:CPU"


def _synthetic():
    ms = 1_000_000.0
    return [
        (HOST, "python", "bench.window", 0.0, 10 * ms),
        (HOST, "python", "bench.step", 0.0, 10 * ms),
        (HOST, "python", "bench.phase_begin", 0.0, 2 * ms),
        (HOST, "python", "bench.phase_finish", 8 * ms, 10 * ms),
        (DEV, "XLA Ops", "fusion.1", 2 * ms, 3 * ms),
        (DEV, "XLA Ops", "%segment_sum_pallas.2 = f32[8] custom-call()",
         3 * ms, 7 * ms),
        (DEV, "XLA Ops", "fusion.3", 6 * ms, 7.5 * ms),   # overlaps
        (DEV, "XLA Ops", "fusion.1", 11 * ms, 12 * ms),    # after window
        (DEV, "XLA Modules", "jit_pipeline", 2 * ms, 7.5 * ms),
    ]


def test_reduce_synthetic_window():
    r = trace.reduce(_synthetic(), {"segsum_s": r"^segment_sum"})
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.0055)       # union 2..7.5 ms
    assert r["kernel_s"]["segsum_s"] == pytest.approx(0.004)
    assert r["device_ops"][0] == ["segment_sum_pallas.2", pytest.approx(0.004)]
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.phase_begin"] == pytest.approx(0.002)
    assert gaps["bench.phase_finish"] == pytest.approx(0.0025)


def test_no_device_work_is_an_error():
    events = [e for e in _synthetic() if e[0] == HOST]
    with pytest.raises(RuntimeError, match="no device operation"):
        trace.reduce(events, {})


def test_recorded_chip_trace():
    """Six phases of aries12.halo3d512_protocol traced on a TPU v5e: every
    device operation of the window and the longer host spans.  The
    numbers are those the reduction gave on the chip from the whole
    trace."""
    with gzip.open(DATA / "halo3d512_trace.json.gz", "rt") as f:
        events = [tuple(e) for e in json.load(f)]
    r = trace.reduce(events, {"segsum_s": r"^segment_sum"})
    assert r["window_s"] == pytest.approx(0.181861851, rel=1e-6)
    assert r["busy_s"] == pytest.approx(0.041680093, rel=1e-6)
    assert r["kernel_s"]["segsum_s"] == pytest.approx(0.032394371, rel=1e-6)
    kernels = [e for e in events if e[2].startswith("%segment_sum")]
    assert len(kernels) == 6 * 6          # five pair sums + one NIC sum
    idle = sum(s for _, s in trace.reduce(events, {}, top=100)["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-9)
