from repro.kernels.segment_sum.ref import segment_sum_ref
from repro.kernels.segment_sum.segment_sum import segment_sum_pallas

__all__ = ["segment_sum_ref", "segment_sum_pallas"]
