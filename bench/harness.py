"""One run of one cell: set-up, warm-up, the measured window, the check.

Everything a cell is made of is found by name: its entry in
``BENCHMARK.json``, its machine in ``bench/configs/<config>.json`` (with
the reference's path model in ``bench/machines/<family>.py``), its
traffic in ``bench/traffic/<traffic>.json`` (read by
``bench/kinds/<kind>.py``), its shape band and the limits of its check
in ``bench/cells/<cell>.json``, and each per-layer metric's reader in
``bench/metrics/<metric>.py``.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import os
import pathlib
import sys
import tempfile
import time

import numpy as np

from bench import check, traffic
from bench import trace as tracing

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
#: the persistent compilation cache when JAX_COMPILATION_CACHE_DIR is
#: unset: one fixed directory in the checkout (the path keys the cache)
CACHE_DIR = ROOT / ".jax_cache"
#: jax.monitoring events that mean something was traced or compiled
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
MAX_PHASES = 1 << 20


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


class RunFault(RuntimeError):
    """The run broke a condition its numbers depend on."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def cell_spec(name: str, benchmark: dict | None = None) -> dict:
    """The cell ``name`` with its configuration, traffic, cell file and
    the metrics it reports."""
    if benchmark is None:
        benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    (w,) = [w for w in benchmark["workloads"] if w["name"] == name]

    def ours(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {"name": name, "chips": w["chips"],
            "config": traffic.load_json("configs", w["config"]),
            "mix": traffic.load_json("traffic", w["traffic"]),
            "cell": traffic.load_json("cells", name),
            "end_to_end": ours(benchmark["end_to_end"]),
            "per_layer": ours(benchmark["per_layer"])}


def peak_of(kind: str) -> dict:
    """The published peaks of one device kind; an unknown kind is an
    error, never a default."""
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def use_compile_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter(contextlib.AbstractContextManager):
    """Counts traces, lowerings and compiles (cache loads included)
    while it is entered."""

    def __enter__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.n += 1


def _pipeline_calls() -> int:
    from repro.dragonfly.jax_backend import PIPELINE_CALLS
    return sum(PIPELINE_CALLS.values())


def _annotate(sim) -> None:
    """Host spans around the calls into each layer of a phase."""
    import jax
    for name in ("_phase_begin", "_run_kernel", "_phase_finish"):
        def wrapped(*a, _fn=getattr(sim, name),
                    _label="bench." + name.strip("_"), **k):
            with jax.profiler.TraceAnnotation(_label):
                return _fn(*a, **k)
        setattr(sim, name, wrapped)


def sampler(mix: dict, seed: int):
    """The phases the check compares, drawn from the seed: the window's
    first phase, then each with probability ``check_share``.  Returns
    (a one-item list to set to the window's first phase, the test)."""
    first = [MAX_PHASES]
    pick = np.random.default_rng([seed, 1]).random(MAX_PHASES) \
        < mix["check_share"]

    def sampled(i):
        return first[0] <= i < MAX_PHASES and (i == first[0] or pick[i])
    return first, sampled


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True) -> dict:
    """One run; returns the result line as a dict."""
    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < spec["chips"]):
        raise NoChip(f"needs {spec['chips']} TPU chip(s); jax found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    if require_tpu:
        log(f"compile cache: {use_compile_cache()}")
    with CompileCounter() as counter, \
            tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        return _run(spec, seed, seconds, trace, t_start, require_tpu,
                    devices, counter, tdir)


def _run(spec, seed, seconds, trace, t_start, require_tpu, devices,
         counter, tdir) -> dict:
    import jax
    mix, cfg, cell = spec["mix"], spec["config"], spec["cell"]

    first, sampled = sampler(mix, seed)
    params = dict(cfg["sim"], **cfg["program"], profile_stages=trace)
    driver = traffic.Driver(mix, cfg, seed, cell.get("plan_pairs"), params,
                            sampled)
    calls = _pipeline_calls()
    for _ in range(mix["warmup_units"]):
        driver.step()
    if _pipeline_calls() - calls != driver.phases_run:
        raise RunFault(f"{_pipeline_calls() - calls} jitted dispatches for "
                       f"{driver.phases_run} warm-up phases")
    if require_tpu:
        from repro.dragonfly.jax_backend import kernel_mode
        if kernel_mode(driver.sim.params) != (True, False):
            raise RunFault("the Pallas segment-sum is not running compiled")
    first[0] = driver.phases_run
    log(f"set-up: {driver.phases_run} warm-up phases, {counter.n} compile "
        f"events, plan pairs "
        f"{[int(p.pair_links.shape[0]) for p in driver.plans]}")

    if trace:
        _annotate(driver.sim)
    driver.sim.stage_time_s.clear()
    compiles, calls = counter.n, _pipeline_calls()
    phases = 0
    with contextlib.ExitStack() as stack:
        if trace:
            stack.enter_context(tracing.capture(tdir))
        t0 = time.perf_counter()
        ends = [t0]
        with (jax.profiler.TraceAnnotation(tracing.WINDOW) if trace
              else contextlib.nullcontext()):
            while True:
                phases += driver.step()
                t1 = time.perf_counter()
                ends.append(t1)
                if t1 - t0 >= seconds or (
                        trace and phases >= mix["trace_phases"]):
                    break
    steps = np.diff(ends)
    log(f"window: {phases} phases in {t1 - t0} s, {counter.n - compiles} "
        f"compile events; a step took {steps.min()} / {np.median(steps)} "
        f"/ {steps.max()} s (least / median / most)")
    if counter.n != compiles:
        raise RunFault(f"{counter.n - compiles} compile events in the window")
    if _pipeline_calls() - calls != phases:
        raise RunFault(f"{_pipeline_calls() - calls} jitted dispatches for "
                       f"{phases} phases in the window")
    stats = devices[0].memory_stats() or {}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}

    summary = None
    if trace:
        patterns = {}
        for m in spec["per_layer"]:
            mod = importlib.import_module(f"bench.metrics.{m['name']}")
            if hasattr(mod, "PATTERN"):
                patterns[m["name"]] = mod.PATTERN
        summary = tracing.reduce(tracing.load(tdir), patterns)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]

    traced = range(first[0], first[0] + phases) if trace else ()
    t_check = time.perf_counter()
    res = check.compare(driver, counts_for=traced)
    limits = cell["limits"]
    correct = check.verdict(res["program"], limits)

    if not trace:
        values = {"phase_s": (t1 - t0) / phases, "setup_s": t0 - t_start}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    else:
        obs = {"phases": phases, "stages_s": dict(driver.sim.stage_time_s),
               "trace": summary, "counts": res["counts"],
               "links": driver.mach.n_links,
               "reductions": cfg["sim"]["route_feedback_iters"] + 1,
               "peak": peak_of(device["kind"])}
        metrics = {}
        for m in spec["per_layer"]:
            v = importlib.import_module(f"bench.metrics.{m['name']}").read(obs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    numbers = {name: {"value": res["program"].get(name), "limit": lim}
               for name, lim in limits.items()}
    log(f"check: {res['phases']} phases, {res['flows']} app flows compared "
        f"with the plain reference in {time.perf_counter() - t_check} s")
    for name, v in numbers.items():
        log(f"check {name} = {v['value']!r} (limit {v['limit']!r})")
    out = {"correct": correct, "attempted": phases, "failed": 0,
           "metrics": metrics, "device": device}
    if summary is not None:
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["check"] = numbers
    return out
