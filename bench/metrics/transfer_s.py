"""Host seconds per phase of the simulator's ``transfer`` stage, the
profiler span ``df.transfer`` (``SimParams.profile_stages``): the host's
hand-over of every per-phase input to the device
(``jax_backend._prepare_inputs``): the background tails and their
donated write into the pinned buffers, the per-phase arrays, the
scalars, and on a plan's first phase its pinning.

Read over the traced window; None where the program has no such stage."""

LAYER = "host-device transfer"
MOVES = "phase_s"
STAGE = "transfer"


def read(obs):
    st = obs["stages_s"]
    if not obs["phases"] or STAGE not in st:
        return None
    return st[STAGE] / obs["phases"]
