"""Balanced Dragonfly (Kim, Dally, Scott, Abts, ISCA 2008): the plain
reference's link numbering, capacities and candidate paths, from the
configuration's ``machine`` numbers alone.  Found by its ``family``
name."""

from __future__ import annotations

import numpy as np

from bench.reference import MAX_HOPS, PAD


class Machine:
    """Balanced Dragonfly (Kim et al., ISCA 2008): g groups of a routers
    with p nodes and h global ports each, all-to-all local links, global
    channels laid out in the palm-tree (or consecutive) arrangement."""

    def __init__(self, m: dict):
        self.p, self.a, self.h = m["p"], m["a"], m["h"]
        self.g = m.get("g") or self.a * self.h + 1
        self.palmtree = m.get("arrangement", "palmtree") == "palmtree"
        self.rounds = (self.a * self.h) // (self.g - 1)
        self.n_groups = self.g
        self.n_nodes = self.g * self.a * self.p
        self.nodes_per_group = self.a * self.p
        self.glob_off = self.g * self.a * self.a
        self.nic_off = self.glob_off + self.g * self.a * self.h
        self.n_links = self.nic_off + self.n_nodes
        cap = np.full(self.n_links, float(m["local_gbs"]))
        cap[self.glob_off:self.nic_off] = m["global_gbs"]
        cap[self.nic_off:] = m["nic_gbs"]
        self.capacity_gbs = cap
        # ids of real links: ordered router pairs of a group, the global
        # channels and the NICs (a router's link to itself is unused)
        self.n_real_links = self.g * self.a * (self.a - 1) \
            + self.g * self.a * self.h + self.n_nodes
        self.hop_latency_ns = float(m["hop_latency_ns"])
        self.nic_latency_ns = float(m["nic_latency_ns"])

    def nic_link(self, node):
        return self.nic_off + np.asarray(node)

    def _chan(self, g_from, g_to, j):
        m = (g_from - g_to - 1) % self.g if self.palmtree \
            else (g_to - g_from - 1) % self.g
        return j * (self.g - 1) + m

    def _local(self, grp, r1, r2):
        return (grp * self.a + r1) * self.a + r2

    def _global(self, grp, c):
        return self.glob_off + grp * (self.a * self.h) + c

    def choices(self, n, rng, n_min, n_nonmin) -> dict:
        return {"k0": rng.integers(0, self.rounds, size=n),
                "gis": rng.integers(0, self.g, size=(n_nonmin, n)),
                "knm": rng.integers(0, self.rounds, size=(2 * n_nonmin, n))}

    def paths(self, src, dst, ch, n_min, n_nonmin):
        src = np.asarray(src, dtype=np.int64)
        dst = np.asarray(dst, dtype=np.int64)
        n = src.shape[0]
        k0, gis, knm = ch["k0"], ch["gis"], ch["knm"]
        g1, l1 = np.divmod(src // self.p, self.a)
        g2, l2 = np.divmod(dst // self.p, self.a)
        intra, x = g1 == g2, g1 != g2
        out = np.full((n, n_min + n_nonmin, MAX_HOPS), PAD, dtype=np.int64)

        def put(col, slot, mask, links):
            out[mask, col, slot] = links[mask]

        for j in range(n_min):
            k = (k0 + j) % self.rounds
            c1, c2 = self._chan(g1, g2, k), self._chan(g2, g1, k)
            gw1, gw2 = c1 // self.h, c2 // self.h
            put(j, 0, intra & (l1 != l2), self._local(g1, l1, l2))
            put(j, 0, x & (l1 != gw1), self._local(g1, l1, gw1))
            put(j, 1, x, self._global(g1, c1))
            put(j, 2, x & (gw2 != l2), self._local(g2, gw2, l2))
        for j in range(n_nonmin):
            col = n_min + j
            gi, j1, j2 = gis[j], knm[2 * j], knm[2 * j + 1]
            ri = (gi * 40503 + 7) % self.a
            put(col, 0, intra & (l1 != ri), self._local(g1, l1, ri))
            put(col, 1, intra & (ri != l2), self._local(g1, ri, l2))
            gm = gi % self.g
            for _ in range(2):
                gm = np.where((gm == g1) | (gm == g2), (gm + 1) % self.g, gm)
            ca, cb = self._chan(g1, gm, j1), self._chan(gm, g2, j2)
            gwa, xb = ca // self.h, cb // self.h     # exit routers
            ea = self._chan(gm, g1, j1) // self.h    # entry routers
            eb = self._chan(g2, gm, j2) // self.h
            put(col, 0, x & (l1 != gwa), self._local(g1, l1, gwa))
            put(col, 1, x, self._global(g1, ca))
            put(col, 2, x & (ea != xb), self._local(gm, ea, xb))
            put(col, 3, x, self._global(gm, cb))
            put(col, 4, x & (eb != l2), self._local(g2, eb, l2))
        out[src == dst] = PAD
        return out
