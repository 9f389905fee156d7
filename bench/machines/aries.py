"""Cray Aries (arXiv:1909.07865 §2.1): the plain reference's link
numbering, capacities and candidate paths, from the configuration's
``machine`` numbers alone.  Found by its ``family`` name."""

from __future__ import annotations

import numpy as np

from bench.reference import MAX_HOPS, PAD


class Machine:
    """Cray Aries: groups of chassis x blades routers, all-to-all chassis
    (green) and row (black) links inside a group, K global links between
    every pair of groups, one NIC link per node.  Directed link ids."""

    def __init__(self, m: dict):
        self.G, self.C = m["n_groups"], m["chassis_per_group"]
        self.B, self.P = m["blades_per_chassis"], m["nodes_per_blade"]
        self.K = m["global_links_per_pair"]
        G, C, B, K = self.G, self.C, self.B, self.K
        self.R = C * B                      # routers per group
        self.n_groups = G
        self.n_nodes = G * self.R * self.P
        self.nodes_per_group = self.R * self.P
        self.row_off = G * C * B * B * 2
        self.glob_off = self.row_off + G * C * C * B * 2
        self.nic_off = self.glob_off + G * G * K * 2
        self.n_links = self.nic_off + self.n_nodes
        cap = np.full(self.n_links, float(m["electrical_gbs"]))
        # parallel row links of one router pair act as one link of their
        # summed rate in the fluid model
        cap[self.row_off:self.glob_off] *= m["row_links_per_pair"]
        cap[self.glob_off:self.nic_off] = m["optical_gbs"]
        cap[self.nic_off:] = m["nic_gbs"]
        self.capacity_gbs = cap
        # ids of real links: ordered router pairs of a chassis, of a row,
        # of two groups, and the NICs (the rest of the id space is unused)
        self.n_real_links = G * C * B * (B - 1) + G * B * C * (C - 1) \
            + G * (G - 1) * K + self.n_nodes
        self.hop_latency_ns = float(m["hop_latency_ns"])
        self.nic_latency_ns = float(m["nic_latency_ns"])

    def nic_link(self, node):
        return self.nic_off + np.asarray(node)

    def _coords(self, node):
        router, _ = np.divmod(np.asarray(node, dtype=np.int64), self.P)
        g, r = np.divmod(router, self.R)
        c, b = np.divmod(r, self.B)
        return g, c, b

    def _chassis(self, g, c, b1, b2):
        lo, hi = np.minimum(b1, b2), np.maximum(b1, b2)
        return (((g * self.C + c) * self.B + lo) * self.B + hi) * 2 + (b1 > b2)

    def _row(self, g, c1, c2, b):
        lo, hi = np.minimum(c1, c2), np.maximum(c1, c2)
        return self.row_off + (((g * self.C + lo) * self.C + hi) * self.B
                               + b) * 2 + (c1 > c2)

    def _global(self, g1, g2, k):
        lo, hi = np.minimum(g1, g2), np.maximum(g1, g2)
        return self.glob_off + ((lo * self.G + hi) * self.K + k) * 2 \
            + (g1 > g2)

    def _gateway(self, g_here, g_there, k):
        """(chassis, blade) of the router of g_here that owns global link
        k toward g_there."""
        h = (np.asarray(g_there) * self.K + np.asarray(k)) \
            * np.int64(2654435761) + np.asarray(g_here)
        return np.divmod(np.abs(h) % self.R, self.B)

    def _inside(self, g, c1, b1, c2, b2, chassis_first):
        """Route of at most two hops inside a group -> [n, 2]."""
        out = np.full((g.shape[0], 2), PAD, dtype=np.int64)
        same_c, same_b = c1 == c2, b1 == b2
        one = same_c ^ same_b                # one hop: chassis or row
        out[:, 0] = np.where(one & same_c, self._chassis(g, c1, b1, b2),
                             np.where(one, self._row(g, c1, c2, b1), PAD))
        two = ~same_c & ~same_b
        cf = two & chassis_first             # chassis, then row
        rf = two & ~chassis_first            # row, then chassis
        out[cf, 0] = self._chassis(g, c1, b1, b2)[cf]
        out[cf, 1] = self._row(g, c1, c2, b2)[cf]
        out[rf, 0] = self._row(g, c1, c2, b1)[rf]
        out[rf, 1] = self._chassis(g, c2, b1, b2)[rf]
        return out

    def choices(self, n, rng, n_min, n_nonmin) -> dict:
        """The random choices of n flows' candidates, drawn from the
        stream in the simulator's order; flow i's sit at index i of the
        last axis, so a flow's paths can be rebuilt alone."""
        return {"k0": rng.integers(0, self.K, size=n),
                "gis": rng.integers(0, max(self.G, 1), size=(n_nonmin, n)),
                "knm": rng.integers(0, self.K, size=(2 * n_nonmin, n)),
                "seeds": rng.integers(0, 4, size=(n_min, n))}

    def paths(self, src, dst, ch, n_min, n_nonmin):
        """[n, n_min + n_nonmin, MAX_HOPS] link ids, PAD-padded."""
        src = np.asarray(src, dtype=np.int64)
        dst = np.asarray(dst, dtype=np.int64)
        n = src.shape[0]
        k0, gis, knm, seeds = ch["k0"], ch["gis"], ch["knm"], ch["seeds"]
        g1, c1, b1 = self._coords(src)
        g2, c2, b2 = self._coords(dst)
        intra = g1 == g2
        out = np.full((n, n_min + n_nonmin, MAX_HOPS), PAD, dtype=np.int64)
        for j in range(n_min):
            k = (k0 + j) % self.K
            s = seeds[j]
            gc1, gb1 = self._gateway(g1, g2, k)
            gc2, gb2 = self._gateway(g2, g1, k)
            first = np.where(intra, (s + k) % 2 == 1, s % 2 == 1)
            out[:, j, 0:2] = self._inside(g1, c1, b1, np.where(intra, c2, gc1),
                                          np.where(intra, b2, gb1), first)
            out[~intra, j, 2] = self._global(g1, g2, k)[~intra]
            out[~intra, j, 3:5] = self._inside(g2, gc2, gb2, c2, b2,
                                               (s // 2) % 2 == 1)[~intra]
        yes, no = np.ones(n, dtype=bool), np.zeros(n, dtype=bool)
        for j in range(n_nonmin):
            col = n_min + j
            gi, k1, k2 = gis[j], knm[2 * j], knm[2 * j + 1]
            # a group's own flow detours through a hashed router
            ci, bi = np.divmod((gi * 40503 + 7) % self.R, self.B)
            a = self._inside(g1, c1, b1, ci, bi, yes)
            b = self._inside(g1, ci, bi, c2, b2, no)
            out[intra, col, 0:2] = a[intra]
            out[intra, col, 2:4] = b[intra]
            # other flows go through one intermediate group
            gm = gi % self.G
            for _ in range(2):
                gm = np.where((gm == g1) | (gm == g2), (gm + 1) % self.G, gm)
            gc1, gb1 = self._gateway(g1, gm, k1)
            ec, eb = self._gateway(gm, g1, k1)
            xc, xb = self._gateway(gm, g2, k2)
            gc2, gb2 = self._gateway(g2, gm, k2)
            x = ~intra
            out[x, col, 0:2] = self._inside(g1, c1, b1, gc1, gb1, yes)[x]
            out[x, col, 2] = self._global(g1, gm, k1)[x]
            out[x, col, 3:5] = self._inside(gm, ec, eb, xc, xb, yes)[x]
            out[x, col, 5] = self._global(gm, g2, k2)[x]
            out[x, col, 6:8] = self._inside(g2, gc2, gb2, c2, b2, no)[x]
        out[src == dst] = PAD
        return out
