"""The paper's benchmark data generator (Listing 12), in NumPy.

Engblom & Lukarski, "Fast Matlab compatible sparse assembly on
multicore computers" (arXiv:1406.1066), Listing 12::

    function [ii,jj,ss,siz] = ransparse(siz,nnz_row,nrep)
    % input: size, nonzeros per row, and collisions per final element

Every row holds ``nnz_row`` triplets with uniform random columns, the
whole stream is repeated ``nrep`` times and shuffled.  Table 4.1 sets
1-3 all have ``siz * nnz_row * nrep = 2.5e6`` triplets.

The paper's values are all ones; here they are seeded uniform values in
[0.5, 1.5), so that a sum computed in a lower precision than float32
shows (small integers sum exactly even in bfloat16).
"""
from __future__ import annotations

import numpy as np


def ransparse(siz: int, nnz_row: int, nrep: int, rng: np.random.Generator):
    """Unit-offset int32 ``(ii, jj)`` of one Listing 12 structure."""
    ii = np.repeat(np.arange(1, siz + 1, dtype=np.int32), nnz_row)
    jj = rng.integers(1, siz + 1, size=siz * nnz_row, dtype=np.int32)
    ii = np.tile(ii, nrep)
    jj = np.tile(jj, nrep)
    p = rng.permutation(ii.size)
    return ii[p], jj[p]


def generate(cfg: dict, seed: int, k: int = 0):
    """Structure ``k`` of the configuration for ``seed``:
    ``(ii, jj, (M, N), state)``.  Every ``k`` gives a new structure of
    the same sizes."""
    siz = int(cfg["siz"])
    rng = np.random.default_rng([int(seed), 0x41, int(k)])
    ii, jj = ransparse(siz, int(cfg["nnz_row"]), int(cfg["nrep"]), rng)
    return ii, jj, (siz, siz), {"L": ii.size}


def value_set(state: dict, seed: int, k: int) -> np.ndarray:
    """Float64 value set ``k``: seeded uniform values in [0.5, 1.5)."""
    rng = np.random.default_rng([int(seed), 0x55, int(k)])
    return rng.random(state["L"]) + 0.5
