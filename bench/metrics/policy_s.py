"""Host seconds per phase of the simulator's ``policy`` stage, the profiler
span ``df.policy`` (``SimParams.profile_stages``): the routing policy's
decision before a phase and the publish of the phase's (L, s) to its
TelemetryBus after it (``traffic.run_iteration_engine``), and each
engine-armed tenant's decision in a tenancy round.  Averaged over every
phase of the window, those a static mode routes included.

Read over the traced window; None where the program has no such stage."""

LAYER = "policy"
MOVES = "phase_s"
STAGE = "policy"


def read(obs):
    st = obs["stages_s"]
    if not obs["phases"] or STAGE not in st:
        return None
    return st[STAGE] / obs["phases"]
