"""Reduce a profiler trace of the measured window to device metrics.

``capture(dir)`` runs JAX's profiler around a block; ``load(dir)`` reads
the ``.xplane.pb`` it wrote into plain events ``(plane, line, name,
start_ns, end_ns)``; ``reduce(events, patterns)`` computes, over the
window the harness marked with its ``bench.window`` annotation:

  * the device's busy time: the union of the intervals of the
    operations on each device plane's op line, averaged over devices;
  * the device time of each named kernel: the summed durations of the
    device operations whose name matches the kernel's pattern;
  * the device operations that took most time, by name;
  * the device's idle time, by what the host's main thread (the thread
    that holds the window span) was doing: the innermost host event
    covering the middle of each idle gap.

A device operation's name is its HLO instruction's name, such as
``segment_sum_pallas.13`` (the trace gives the whole instruction text).
"""

from __future__ import annotations

import contextlib
import pathlib
import re

import numpy as np

#: device planes are named "/device:TPU:<n>"; their operations sit on
#: the "XLA Ops" line
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"


def op_name(text: str) -> str:
    """``%name.3 = f32[..] op(..)`` -> ``name.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


@contextlib.contextmanager
def capture(trace_dir):
    import jax
    jax.profiler.start_trace(str(trace_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(trace_dir) -> list:
    """Every event of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file, found {paths}")
    data = ProfileData.from_file(str(paths[0]))
    return [(plane.name, line.name, ev.name, float(ev.start_ns),
             float(ev.end_ns))
            for plane in data.planes for line in plane.lines
            for ev in line.events]


def _union(intervals) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(events, patterns: dict, top: int = 10) -> dict:
    """Busy and idle time, kernel times, top operations and labelled idle
    gaps of the window.  Seconds throughout."""
    wins = [(l, s, e) for p, l, n, s, e in events
            if p == HOST_PLANE and n == WINDOW]
    if len(wins) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, found {len(wins)}")
    main, w0, w1 = wins[0]
    ops: dict = {}
    for p, l, n, s, e in events:
        if DEVICE_PLANE.match(p) and l == OP_LINE:
            s, e = max(s, w0), min(e, w1)
            if e > s:
                ops.setdefault(p, []).append((op_name(n), s, e))
    if not ops:
        raise RuntimeError("no device operation ran in the window")
    busy_ns, by_name = 0.0, {}
    kernel_ns = {k: 0.0 for k in patterns}
    regex = {k: re.compile(v) for k, v in patterns.items()}
    unions = {}
    for plane, evs in ops.items():
        unions[plane] = _union((s, e) for _, s, e in evs)
        busy_ns += sum(e - s for s, e in unions[plane])
        for n, s, e in evs:
            by_name[n] = by_name.get(n, 0.0) + (e - s)
            for k, rx in regex.items():
                if rx.search(n):
                    kernel_ns[k] += e - s
    n_dev = len(ops)
    host = [(s, e, n) for p, l, n, s, e in events
            if p == HOST_PLANE and l == main and n != WINDOW]
    hs = np.array([h[0] for h in host])
    he = np.array([h[1] for h in host])
    idle: dict = {}
    for plane, busy in unions.items():
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            mid = 0.5 * (s + e)
            cover = np.flatnonzero((hs <= mid) & (mid < he))
            # innermost: the latest start, then the earliest end
            label = host[cover[np.lexsort((he[cover], -hs[cover]))[0]]][2] \
                if cover.size else "(no host span)"
            idle[label] = idle.get(label, 0.0) + (e - s) / n_dev
    window_s = (w1 - w0) * 1e-9

    def ranked(d):
        return [[k, v * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"window_s": window_s, "busy_s": busy_ns / n_dev * 1e-9,
            "devices": n_dev,
            "kernel_s": {k: v / n_dev * 1e-9 for k, v in kernel_ns.items()},
            "device_ops": ranked({k: v / n_dev for k, v in by_name.items()}),
            "idle_gaps": ranked(idle)}
