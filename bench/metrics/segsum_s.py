"""Device seconds per phase of the link-load segment sum, from the
profiler trace: the summed durations of the device operations whose
name matches ``PATTERN``, over the traced phases.

Whatever implements this reduction keeps a name that ``PATTERN`` finds;
where none matches, the metric is left out of the line."""

LAYER = "kernel"
MOVES = "phase_s"
#: device operations of the segment sum: on a TPU v5e the Pallas kernel
#: (kernels/segment_sum) runs as ``segment_sum_pallas.<n>`` custom calls,
#: six per phase (five over the link-flow pairs, one over the NIC rows)
PATTERN = r"^segment_sum"


def read(obs):
    tr = obs["trace"]
    t = tr["kernel_s"].get("segsum_s") if tr else None
    if not t or not obs["phases"]:
        return None
    return t / obs["phases"]

