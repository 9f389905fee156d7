"""chip_smoke.py: no CPU mode, and its phases at a tiny size.

The script's device check lives in ``main()``: off a TPU it exits
nonzero before running anything and never prints the ``"ok"`` line.
The phase functions take the machine and flow count as arguments, so
here they run on a small Aries machine on the CPU (rehearsal of the
control flow and the numpy parity checks, not a measurement).
"""

import importlib.util
import pathlib

import numpy as np
import pytest

from repro.dragonfly import DragonflyTopology, TopologyParams

ROOT = pathlib.Path(__file__).resolve().parent.parent
TOPO = DragonflyTopology(TopologyParams(n_groups=4, chassis_per_group=2,
                                        blades_per_chassis=4))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_exits_nonzero_off_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_main_phase_dispatches_and_matches_numpy(smoke):
    out = smoke.run_main(TOPO, 300, seed=1, steady=1)
    assert out["links"] == TOPO.n_links
    assert out["rows"] == 300 + 16          # app flows + background
    for knob in ("auto", "off"):
        assert out[knob]["pipeline_calls"] == 2
        assert out[knob]["interpret"] is False
        assert out[knob]["max_rel_err"] <= smoke.JAX_RTOL
        assert set(out[knob]["stages_s"]) >= {"fixed_point", "finalize"}


def test_faulted_phase_matches_numpy(smoke):
    out = smoke.run_faulted(TOPO, 300, seed=1)
    assert out["max_rel_err"] <= smoke.JAX_RTOL
    assert out["stranded"] >= 0


def test_notifying_phase_raises_flags(smoke):
    out = smoke.run_notifying(TOPO, 300, seed=1)
    assert 0.0 < out["max_notified"] <= 1.0 + 1e-6
    assert out["max_rel_err"] <= smoke.JAX_RTOL


def test_lockstep_column_uses_vmapped_dispatch(smoke):
    out = smoke.run_lockstep(TOPO, seed=1, ranks=8)
    assert out["cells"] == 2 and out["batched_calls"] >= 1


def test_parity_rejects_drift(smoke):
    """A jax result outside the pinned tolerance fails the phase."""
    from repro.dragonfly.simulator import FlowResult

    def res(t):
        t = np.asarray(t, dtype=np.float64)
        return FlowResult(t_us=t, latency_us=t, stalls_per_flit=t * 0,
                          flits=t, packets=t, nonmin_fraction=0.0)

    assert smoke.parity([res([1.0, 2.0])], [res([1.0, 2.0])]) == {
        "max_rel_err": 0.0, "stall_max_abs_err": 0.0}
    with pytest.raises(AssertionError):
        smoke.parity([res([1.0, 2.1])], [res([1.0, 2.0])])
