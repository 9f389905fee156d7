"""Accelerator runtime helpers for the simulator's jax backend.

``on_tpu`` and ``resolve_pallas_kernel`` decide how the jitted phase
engine (``SimParams.backend = "jax"``) reduces its link loads.
``enable_compile_cache`` places JAX's persistent compilation cache for
the entry points that run on the chip (``chip_smoke.py``,
``benchmarks/perf_sim.py``, ``benchmarks/run.py``).  There is no
fallback here: a jax backend that cannot run raises where it is used.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the one in-checkout directory of the persistent compilation cache
#: when ``JAX_COMPILATION_CACHE_DIR`` is unset (.gitignore lists it).
#: Fixed, never per-process: the path is part of every cache key.
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def on_tpu() -> bool:
    """Is the default jax backend a TPU?"""
    return jax.default_backend() == "tpu"


#: SimParams.pallas_kernel knob values (docs/performance.md)
PALLAS_KNOBS = ("auto", "on", "off")


def resolve_pallas_kernel(knob: str) -> bool:
    """Resolve the ``SimParams.pallas_kernel`` knob to use-kernel or not.

    "auto" uses the Pallas segment-sum on a TPU, compiled, and
    ``jax.ops.segment_sum`` elsewhere; "on" forces the kernel everywhere
    (in interpret mode off the chip — the parity-testing path); "off"
    never uses it, even on a TPU."""
    if knob == "on":
        return True
    if knob == "off":
        return False
    if knob != "auto":
        raise ValueError(f"unknown pallas_kernel knob {knob!r}; "
                         f"expected one of {PALLAS_KNOBS}")
    return on_tpu()


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    A ``JAX_COMPILATION_CACHE_DIR`` in the environment wins: JAX reads
    it itself and no other directory is set.  Otherwise the cache lives
    in :data:`COMPILE_CACHE_DIR`.  Call it from an entry point before
    the first compile; importing a module never turns the cache on."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
