"""All-to-all: one bulk phase in which every rank sends ``size_per_pair``
bytes to every other rank (the NIC pipelines all destinations at once),
each rank's destinations in rank order."""

from __future__ import annotations

import numpy as np

ALLTOALL = True


def phases(n_ranks: int, size_per_pair: int):
    """The n*(n-1) flows of one all-to-all: [(src ranks, dst ranks,
    bytes)]."""
    src = np.repeat(np.arange(n_ranks), n_ranks - 1)
    nth = np.tile(np.arange(n_ranks - 1), n_ranks)
    dst = nth + (nth >= src)                  # every rank but the sender
    return [(src, dst, np.full(src.shape, float(size_per_pair)))]
