"""repro.kernels — Pallas TPU kernels for the assembly hot spots.

Layout (one directory per kernel, as in DESIGN.md):
  hist/           Part 1: blocked private-counter histogram
  counting_sort/  Part 2: MXU one-hot/triangular placement
  radix_sort/     Parts 1-3: LSD radix-partition planner (multi-digit
                  histogram + placement per 8-11-bit digit; the
                  overflow-free production sort)
  segment_sum/    Part 3/4+post: carry-scan cumsum + sorted segment sum
                  (plain and fused gather+mask variants)
  spmv/           padded-ELL SpMV (FEM example)
  assembly_ops    end-to-end kernel-backed assembly
"""
from .assembly_ops import (
    assemble_pallas,
    fill_fused,
    fill_pallas,
    fill_sharded_pallas,
    multiply_fused,
    plan_pallas,
)
from .common import resolve_interpret
from .counting_sort.ops import counting_sort
from .hist.ops import block_offsets, histogram
from .radix_sort.ops import plan_digit_passes, radix_sort_pair
from .segment_sum.ops import (
    gather2_segment_sum_sorted,
    gather_segment_reduce_sorted,
    gather_segment_sum_sorted,
    segment_sum_sorted,
)
from .segment_sum.segment_sum import (
    blocked_cumsum,
    gather2_masked_cumsum,
    gather_masked_cumsum,
    gather_masked_segscan,
)
from .spmv.ops import csc_to_ell, spmv

__all__ = [
    "assemble_pallas",
    "block_offsets",
    "blocked_cumsum",
    "counting_sort",
    "csc_to_ell",
    "fill_fused",
    "fill_pallas",
    "fill_sharded_pallas",
    "gather2_masked_cumsum",
    "gather2_segment_sum_sorted",
    "gather_masked_cumsum",
    "gather_masked_segscan",
    "gather_segment_reduce_sorted",
    "gather_segment_sum_sorted",
    "histogram",
    "multiply_fused",
    "plan_digit_passes",
    "plan_pallas",
    "radix_sort_pair",
    "resolve_interpret",
    "segment_sum_sorted",
    "spmv",
]
