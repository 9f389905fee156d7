"""Host seconds per phase of the simulator's ``fetch`` stage, the profiler
span ``df.fetch`` (``SimParams.profile_stages``): the pipeline's five
outputs copied back to host float64, after ``device_wait`` has waited
for them.

Read over the traced window; None where the program has no such stage."""

LAYER = "host-device transfer"
MOVES = "phase_s"
STAGE = "fetch"


def read(obs):
    st = obs["stages_s"]
    if not obs["phases"] or STAGE not in st:
        return None
    return st[STAGE] / obs["phases"]
