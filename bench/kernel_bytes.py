"""Bytes that a kernel has to move, computed from shapes.

Each function gives the least HBM traffic of one call: every input read
once and every output written once, at the widths the program uses
(int32 indices, float32 values).  Caches, padding and the per-block
tables of the radix kernels are left out, so these are lower bounds and
a share of the bandwidth roof computed from them never overstates.
These are computed numbers, not measured ones.
"""
from __future__ import annotations

INDEX_BYTES = 4   # int32 perm, slot, keys and positions
VALUE_BYTES = 4   # float32 values and data


def fill_bytes(L: int, nzmax: int) -> int:
    """The served fill ``data = zeros(nzmax).at[slot].add(vals[perm])``:
    read ``vals``, ``perm`` and ``slot`` (L each), write ``data``."""
    return 2 * INDEX_BYTES * L + VALUE_BYTES * L + VALUE_BYTES * nzmax


def radix_histogram_bytes(L: int) -> int:
    """One digit pass's histogram kernel: read the L keys."""
    return INDEX_BYTES * L


def radix_placement_bytes(L: int) -> int:
    """One digit pass's placement kernel: read the L keys, write the L
    landing positions."""
    return 2 * INDEX_BYTES * L
