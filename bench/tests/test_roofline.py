import pytest

from bench import harness
from bench.metrics import segsum_roofline


def test_segsum_bytes_at_the_aries_cell_shapes():
    # seed 0 of the 120,000-flow Aries phase: 3,760,244 pairs, 56,448
    # link ids, five reductions of 8-byte pairs into 4-byte bins
    assert segsum_roofline.segsum_bytes(3_760_244, 56_448, 5) \
        == 5 * (3_760_244 * 8 + 56_448 * 4) == 151_538_720


def test_phase_bytes_adds_the_nic_reduction():
    assert segsum_roofline.phase_bytes(100, 10, 7, 5) \
        == 5 * (800 + 28) + (80 + 28)


def test_roofline_share_of_a_phase():
    obs = {"trace": {"kernel_s": {"segsum_s": 1.0}}, "links": 56_448,
           "reductions": 5, "counts": {0: (3_760_244, 120_016)},
           "peak": harness.peak_of("TPU v5 lite"), "phases": 1}
    b = segsum_roofline.phase_bytes(3_760_244, 120_016, 56_448, 5)
    assert segsum_roofline.read(obs) == pytest.approx(100 * b / 819e9)


def test_nothing_to_read_is_no_number():
    obs = {"trace": {"kernel_s": {"segsum_s": 0.0}}, "counts": {0: (1, 1)},
           "phases": 1}
    assert segsum_roofline.read(obs) is None


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        harness.peak_of("TPU v9 imaginary")


def test_v5e_peaks_are_the_published_ones():
    p = harness.peak_of("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
