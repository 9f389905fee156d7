"""Host seconds per phase of the simulator's ``publish`` stage, the
profiler span ``df.publish`` inside ``df.policy``
(``SimParams.profile_stages``): the publish of a decided phase's
per-flow (L, s) to the policy's TelemetryBus after it
(``engine.bus.publish_flow_arrays`` in ``traffic.run_iteration_engine``).
Averaged over every phase of the window, those a static mode routes
included.

Read over the traced window; None where the program has no such stage."""

LAYER = "policy"
MOVES = "phase_s"
STAGE = "publish"


def read(obs):
    st = obs["stages_s"]
    if not obs["phases"] or STAGE not in st:
        return None
    return st[STAGE] / obs["phases"]
