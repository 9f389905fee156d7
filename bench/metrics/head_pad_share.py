"""Zero-mask pairs that pad the plans' sorted heads, in percent of their
real pairs: ``jax_backend.SEGSUM["head_pad_pairs"]`` over
``SEGSUM["head_pairs"]``.

The program pads a plan's link-sorted head to a bucket that grows with
its size, so that placements of one job size share one compiled shape;
the sorted segment sum visits the pad's pair blocks in the last link
block of every head reduction.  The program counts both once a
dispatched phase, from the shapes, for the whole run (warm-up included);
every dispatched phase of a cell takes the same path, so the totals'
ratio is each phase's.  None where the program has no such counter,
dispatched no sorted head, or the window ran no phase."""

LAYER = "kernel"
MOVES = "phase_s"


def read(obs):
    from repro.dragonfly import jax_backend
    counts = getattr(jax_backend, "SEGSUM", {})
    if not (obs["phases"] and counts.get("head_pairs")) \
            or "head_pad_pairs" not in counts:
        return None
    return 100.0 * counts["head_pad_pairs"] / counts["head_pairs"]
