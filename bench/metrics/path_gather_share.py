"""Share of the slots gathered through the candidates' link ids that the
Pallas path gather did, in percent: ``jax_backend.GATHER["kernel_slots"]``
over it plus ``GATHER["xla_slots"]``.

The program counts both once a dispatched phase, from the shapes, for
the whole run (warm-up included).  None where the program has no such
counter or gathered nothing."""

LAYER = "kernel"
MOVES = "phase_s"


def read(obs):
    from repro.dragonfly import jax_backend
    counts = getattr(jax_backend, "GATHER", {})
    total = counts.get("kernel_slots", 0) + counts.get("xla_slots", 0)
    if "kernel_slots" not in counts or not total:
        return None
    return 100.0 * counts["kernel_slots"] / total
