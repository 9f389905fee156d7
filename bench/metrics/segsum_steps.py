"""Pallas segment-sum grid steps per dispatched phase:
``jax_backend.SEGSUM["grid_steps"]`` over the jitted dispatches
(``jax_backend.PIPELINE_CALLS``).

The program counts each phase's kernel calls on the host from the
shapes it dispatches: the dense kernel's segment blocks times pair
blocks, the sorted kernel's visits.  The counters run for the whole
run, warm-up included, and every dispatched phase of a cell takes the
same path, so the mean is the per-phase value (in a cell whose unit
runs several phases of different sizes, their mean).  None where the
program has no such counter or dispatched nothing."""

LAYER = "kernel"
MOVES = "phase_s"


def read(obs):
    from repro.dragonfly import jax_backend
    counts = getattr(jax_backend, "SEGSUM", {})
    calls = sum(jax_backend.PIPELINE_CALLS.values())
    if "grid_steps" not in counts or not calls:
        return None
    return counts["grid_steps"] / calls
