"""The link-load segment sum's share of its roofline, in percent.

The least time the chip could take for a phase's reductions, over the
device time they took (``segsum_s``).  The work is fixed by the
algorithm, not by the implementation: each reduction reads every real
(link id, value) pair once, 4 + 4 bytes, and writes one float32 sum per
bin; padding is not counted.  A phase makes ``route_feedback_iters + 1``
reductions over the link-flow pairs into the links (the first spray,
each feedback iteration, and the backlog load) and one over the phase's
rows into their NIC links.  The adds are negligible against any peak, so
memory bandwidth bounds it."""

LAYER = "kernel"
MOVES = "phase_s"
BOUND = "memory"


def segsum_bytes(pairs: int, links: int, reductions: int) -> int:
    """Bytes that ``reductions`` segment sums of ``pairs`` (id, value)
    pairs into ``links`` bins must move."""
    return reductions * (pairs * 8 + links * 4)


def phase_bytes(pairs: int, rows: int, links: int, reductions: int) -> int:
    return segsum_bytes(pairs, links, reductions) \
        + segsum_bytes(rows, links, 1)


def read(obs):
    tr = obs["trace"]
    t = tr["kernel_s"].get("segsum_s") if tr else None
    if not t or not obs["counts"]:
        return None
    total = sum(phase_bytes(p, r, obs["links"], obs["reductions"])
                for p, r in obs["counts"].values())
    return 100.0 * total / obs["peak"]["hbm_bytes_per_s"] / t
