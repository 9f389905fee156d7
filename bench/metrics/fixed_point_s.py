"""Host seconds per phase of the simulator's ``fixed_point`` stage:
the inputs' transfer to the device, the jitted pipeline, and the fetch
of its results to host numpy, which waits for the device."""

LAYER = "jitted pipeline"
MOVES = "phase_s"


def read(obs):
    st = obs["stages_s"]
    if not obs["phases"] or "fixed_point" not in st:
        return None
    return st["fixed_point"] / obs["phases"]
