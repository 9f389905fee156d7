"""Host seconds per phase of the simulator's ``device_wait`` stage, the
profiler span ``df.device_wait`` (``SimParams.profile_stages``): from
the jitted call until its outputs are ready on the device
(``jax.block_until_ready``, called only under ``profile_stages``): the
dispatch, any input copy still in flight, and the device's work.

Read over the traced window; None where the program has no such stage."""

LAYER = "jitted pipeline"
MOVES = "phase_s"
STAGE = "device_wait"


def read(obs):
    st = obs["stages_s"]
    if not obs["phases"] or STAGE not in st:
        return None
    return st[STAGE] / obs["phases"]
