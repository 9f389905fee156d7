"""Host seconds per phase before and after the jitted pipeline: the
simulator's ``candidates``, ``estimate`` and ``finalize`` stages
(``SimParams.profile_stages``), averaged over the traced phases."""

LAYER = "host prep"
MOVES = "phase_s"
STAGES = ("candidates", "estimate", "finalize")


def read(obs):
    st = obs["stages_s"]
    if not obs["phases"] or not all(k in st for k in STAGES):
        return None
    return sum(st[k] for k in STAGES) / obs["phases"]
