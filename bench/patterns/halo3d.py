"""Ember halo3d: six face exchanges (+-x, +-y, +-z) of a cubic domain
over a near-cubic grid of ranks."""

from __future__ import annotations

import numpy as np

ALLTOALL = False


def grid_dims(n: int, dims: int) -> list:
    """Near-cubic factorisation of n into ``dims`` factors, largest
    first, as MPI_Dims_create does."""
    out, f, primes, d = [1] * dims, n, [], 2
    while d * d <= f:
        while f % d == 0:
            primes.append(d)
            f //= d
        d += 1
    if f > 1:
        primes.append(f)
    for p in sorted(primes, reverse=True):
        out[out.index(min(out))] *= p
    return sorted(out, reverse=True)


def phases(n_ranks: int, nx: int, var_bytes: int = 8, vars_: int = 1):
    """The six exchanges of a domain of edge ``nx``:
    [(src ranks, dst ranks, bytes)]."""
    px, py, pz = grid_dims(n_ranks, 3)
    lx, ly, lz = nx // px, nx // py, nx // pz
    face = (ly * lz, lx * lz, lx * ly)
    ranks = np.arange(n_ranks)
    z, rem = np.divmod(ranks, px * py)
    y, x = np.divmod(rem, px)
    coords, dims, out = (x, y, z), (px, py, pz), []
    for axis in range(3):
        for sign in (1, -1):
            nb = [c.copy() for c in coords]
            nb[axis] = coords[axis] + sign
            ok = (nb[axis] >= 0) & (nb[axis] < dims[axis])
            dst = nb[0] + nb[1] * px + nb[2] * px * py
            out.append((ranks[ok], dst[ok],
                        np.full(int(ok.sum()),
                                float(face[axis] * var_bytes * vars_))))
    return out
