"""Device seconds per phase of the Pallas path gather, from the profiler
trace: the summed durations of the device operations whose name
matches ``PATTERN``, over the traced phases.

None where no operation matches: the program gathers through XLA there
(off the chip, above the engine's table-size threshold, or a program
without the kernel)."""

LAYER = "kernel"
MOVES = "phase_s"
#: device operations of the path gather: on a TPU v5e the Pallas kernel
#: (kernels/path_gather) runs as ``path_gather_pallas.<n>`` custom calls,
#: 3 + the feedback iterations per phase
PATTERN = r"^path_gather"


def read(obs):
    tr = obs["trace"]
    t = tr["kernel_s"].get("path_gather_s") if tr else None
    if not t or not obs["phases"]:
        return None
    return t / obs["phases"]
