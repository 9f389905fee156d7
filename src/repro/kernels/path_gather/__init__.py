from repro.kernels.path_gather.path_gather import (
    index_rows, path_gather_pallas)

__all__ = ["index_rows", "path_gather_pallas"]
