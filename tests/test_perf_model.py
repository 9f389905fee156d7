"""Paper §2.4 performance model — unit + hypothesis property tests."""

import math

import pytest
from hypothesis import given, strategies as st

from repro.core.perf_model import (
    MessageShape, flit_threshold, flits_and_packets,
    predict_transmission_cycles, transmission_cycles_eq1,
    transmission_cycles_eq2, MAX_OUTSTANDING_PACKETS,
)


def test_put_flit_packet_counts():
    # 1 packet per 64B; PUT = 1 header + 4 payload flits
    f, p = flits_and_packets(64, is_put=True)
    assert p == 1 and f == 5
    f, p = flits_and_packets(128, is_put=True)
    assert p == 2 and f == 10


def test_get_flit_counts():
    f, p = flits_and_packets(256, is_put=False)
    assert p == 4 and f == 4  # GET requests carry no payload flits


def test_short_tail_packet():
    # 96B = one full packet + 32B tail (2 payload flits + header)
    f, p = flits_and_packets(96, is_put=True)
    assert p == 2
    assert f == 5 + 3


def test_eq1_eq2_agree_at_single_packet():
    # for p << 1024, Eq2's window term ~ L/2, recovering Eq1
    l, s, f, p = 2000.0, 0.3, 5, 1
    e1 = transmission_cycles_eq1(l, s, f)
    e2 = transmission_cycles_eq2(l, s, f, p)
    assert abs(e1 - e2) / e1 < 0.01


def test_eq2_window_term():
    # 1024 packets => one extra latency per window: coefficient 1.5
    t = transmission_cycles_eq2(1000.0, 0.0, 5 * 1024, 1024)
    assert t == pytest.approx(1.5 * 1000.0 + 5 * 1024)


@given(size=st.integers(64, 1 << 24), l=st.floats(100, 1e5),
       s=st.floats(0, 50))
def test_eq2_monotonic_in_stalls_and_latency(size, l, s):
    base = predict_transmission_cycles(size, l, s)
    assert predict_transmission_cycles(size, l * 1.1, s) > base
    assert predict_transmission_cycles(size, l, s + 0.5) > base
    assert predict_transmission_cycles(size * 2, l, s) > base


@given(l_a=st.floats(100, 1e5), l_b=st.floats(100, 1e5),
       s_a=st.floats(0, 20), s_b=st.floats(0, 20),
       size=st.integers(64, 1 << 22))
def test_flit_threshold_is_the_eq2_crossover(l_a, l_b, s_a, s_b, size):
    """f < threshold <=> Eq2(mode_b) < Eq2(mode_a), within Eq.(4)'s
    validity domain s_b > s_a (the paper's setting: the minimal-biased
    mode stalls more).  Outside it only the dominance corner is defined —
    the router compares Eq.(3) directly there."""
    f, p = flits_and_packets(size)
    thr = flit_threshold(l_a, s_a, l_b, s_b, p)
    tb = transmission_cycles_eq2(l_b, s_b, f, p)
    ta = transmission_cycles_eq2(l_a, s_a, f, p)
    if thr == math.inf:
        # b dominates (never-worse) — Eq2 must agree.  -inf is not this
        # corner: a vanishing s_b - s_a with L_b > L_a overflows the
        # crossover to below every flit count (b never wins)
        assert tb <= ta + 1e-6 * max(ta, 1.0)
    elif s_b > s_a:
        if f < thr:
            assert tb < ta + 1e-6 * max(ta, 1.0)
        elif f > thr * (1 + 1e-9) + 1:
            assert tb >= ta - 1e-6 * max(ta, 1.0)


def test_window_never_below_half():
    # (p+512)/1024 >= ~0.5: the L/2 first-flit flight time survives
    assert MAX_OUTSTANDING_PACKETS == 1024
    t = transmission_cycles_eq2(1000.0, 0.0, 5, 1)
    assert t >= 500.0
