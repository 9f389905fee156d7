"""Bytes handed to the device per dispatched phase, as the host
buffers held them (a float64 buffer converted on the device counts
8 B an element): ``jax_backend.TRANSFER["h2d_bytes"]`` over the
jitted dispatches (``jax_backend.PIPELINE_CALLS``).

The counters run for the whole run, warm-up included: the harness
clears only the stage clock before the window.  Pinning a plan is not
counted, and every dispatched phase of a cell takes the same path,
moving the same copies (tests/test_stage_clock.py); its bytes follow
the phase's size.  So the mean is the per-phase value, and in a cell
whose unit runs several phases of different sizes it is their mean, as
every unit runs all of them.  None where the program has no such
counter or dispatched nothing."""

LAYER = "host-device transfer"
MOVES = "phase_s"
COUNTER = "h2d_bytes"


def read(obs):
    from repro.dragonfly import jax_backend
    counts = getattr(jax_backend, "TRANSFER", {})
    calls = sum(jax_backend.PIPELINE_CALLS.values())
    if COUNTER not in counts or not calls:
        return None
    return counts[COUNTER] / calls
