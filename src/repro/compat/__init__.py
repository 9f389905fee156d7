"""repro.compat — the few helpers the repo layers over the installed jax.

    from repro import compat

    mesh = compat.make_mesh((4, 4), ("data", "model"))   # Auto axes
    with jax.set_mesh(mesh):
        sizes = compat.abstract_axis_sizes()             # {"data": 4, ...}

``compat.runtime`` holds the simulator's accelerator helpers: TPU
detection, the ``SimParams.pallas_kernel`` resolution and the placement
of JAX's persistent compilation cache.  Everything else calls jax
directly (docs/compat.md).
"""

from repro.compat.mesh import abstract_axis_sizes, make_mesh
from repro.compat.runtime import (enable_compile_cache, on_tpu,
                                  resolve_pallas_kernel)

__all__ = [
    "abstract_axis_sizes", "make_mesh",
    "enable_compile_cache", "on_tpu", "resolve_pallas_kernel",
]
