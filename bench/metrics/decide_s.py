"""Host seconds per phase of the simulator's ``decide`` stage, the
profiler span ``df.decide`` inside ``df.policy``
(``SimParams.profile_stages``): the routing policy's decision before a
phase, ``DecisionBatch.of`` and ``engine.decide`` in
``traffic.run_iteration_engine``.  Averaged over every phase of the
window, those a static mode routes included.

Read over the traced window; None where the program has no such stage."""

LAYER = "policy"
MOVES = "phase_s"
STAGE = "decide"


def read(obs):
    st = obs["stages_s"]
    if not obs["phases"] or STAGE not in st:
        return None
    return st[STAGE] / obs["phases"]
