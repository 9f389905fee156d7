"""Share of the traced window in which no operation ran on the device,
in percent: 100 * (1 - busy / window) from the profiler trace."""

LAYER = "device"
MOVES = "phase_s"


def read(obs):
    tr = obs["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
