"""Plain reference of Matlab's ``sparse``, in NumPy.

The benchmark's own copy of the straightforward emulation (stable sort
by (col, row), sum equal keys, CSC out, structural zeros kept); it
imports nothing of the program under test.  :class:`StructureReference`
splits it in two so that a structure that is refilled with many value
vectors is sorted once: the constructor gives ``indptr``, ``indices``
and ``nnz``, and ``values(ss)`` sums a value vector into the
structure's slots in float64.
"""
from __future__ import annotations

import numpy as np


class StructureReference:
    """The sorted structure of one zero-offset triplet stream."""

    def __init__(self, ii, jj, M: int, N: int):
        ii = np.asarray(ii, dtype=np.int64)
        jj = np.asarray(jj, dtype=np.int64)
        self.M, self.N, self.L = int(M), int(N), int(ii.size)
        key = jj * M + ii
        self.order = np.argsort(key, kind="stable")
        skey = key[self.order]
        boundary = np.empty(skey.shape, dtype=bool)
        boundary[:1] = True
        boundary[1:] = skey[1:] != skey[:-1]
        #: output slot of each sorted triplet
        self.slot = np.cumsum(boundary) - 1
        self.nnz = int(boundary.sum())
        ukey = skey[boundary]
        self.indices = (ukey % M).astype(np.int32)
        counts = np.bincount(ukey // M, minlength=N)
        self.indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)

    def values(self, ss) -> np.ndarray:
        """``prS``: the float64 sums of ``ss`` over each entry."""
        ss = np.asarray(ss, dtype=np.float64)[self.order]
        return np.bincount(self.slot, weights=ss, minlength=self.nnz)

    def magnitudes(self, ss) -> np.ndarray:
        """Sum of ``|s|`` over each entry: the scale that a rounding
        error in the entry's sum is measured against."""
        return self.values(np.abs(np.asarray(ss, dtype=np.float64)))
