"""COO triplet container — the raw input of the assembly problem.

Matches the paper's Listing 2: row indices ``ii``, column indices ``jj``
(both *unit-offset* in the Matlab API, stored zero-offset internally),
values ``sr`` and the matrix dimensions ``(M, N)``.

All arrays have static length ``L`` (= the paper's ``len``); JAX/XLA
requires static shapes, so a COO batch is always "full".  Invalid /
padding entries are expressed with ``row == M`` sentinels (they fall off
the end of every histogram) — this is how the distributed all_to_all
padding is represented too.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .spans import UPLOAD, VALIDATE, span


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class COO:
    """Zero-offset COO triplets with static metadata.

    rows, cols : int32[L]   (zero-offset; row == M marks padding)
    vals       : float[L]
    shape      : (M, N)     static python ints
    """

    rows: jax.Array
    cols: jax.Array
    vals: jax.Array
    shape: tuple[int, int] = dataclasses.field(metadata=dict(static=True))

    @property
    def L(self) -> int:
        return int(self.rows.shape[-1])

    @property
    def M(self) -> int:
        return int(self.shape[0])

    @property
    def N(self) -> int:
        return int(self.shape[1])

    def __len__(self) -> int:  # pragma: no cover - convenience
        return self.L

    def to_dense(self) -> jax.Array:
        """Dense scatter-add (duplicates sum; satisfies ``SparseMatrix``)."""
        return coo_to_dense(self.rows, self.cols, self.vals, M=self.M, N=self.N)


def coo_from_matlab(ii, jj, ss, shape=None) -> COO:
    """Build a :class:`COO` from Matlab-style *unit-offset* index vectors.

    Mirrors the pre-processing of the paper's Listing 13: indices are
    validated (integral, >= 1), converted to int32 and the matrix
    dimensions are inferred as the max index when ``shape`` is omitted.
    """
    with span(VALIDATE):
        ii = np.asarray(ii)
        jj = np.asarray(jj)
        ss = np.asarray(ss, dtype=np.float64)
        if ii.shape != jj.shape or ii.shape != ss.shape:
            raise ValueError("i, j, s must have identical shapes")
        if ii.size and (np.any(ii < 1) or np.any(ii != np.floor(ii))):
            raise ValueError("bad row index (must be positive integers)")
        if jj.size and (np.any(jj < 1) or np.any(jj != np.floor(jj))):
            raise ValueError("bad column index (must be positive integers)")
        ii = ii.astype(np.int32).ravel()
        jj = jj.astype(np.int32).ravel()
        ss = ss.ravel()
        if shape is None:
            M = int(ii.max()) if ii.size else 0
            N = int(jj.max()) if jj.size else 0
        else:
            M, N = int(shape[0]), int(shape[1])
            if ii.size and (ii.max() > M or jj.max() > N):
                raise ValueError("index exceeds matrix dimensions")
    # each copy is issued as soon as its host array is made, so making
    # the next one overlaps it
    with span(UPLOAD, bytes=ii.nbytes + jj.nbytes + 4 * ss.size):
        return COO(
            rows=jnp.asarray(ii - 1),
            cols=jnp.asarray(jj - 1),
            vals=upload_values(ss),
            shape=(M, N),
        )


def upload_values(ss: np.ndarray) -> jax.Array:
    """Device float32 copy of flat float64 triplet values: the values
    part of every upload, cold (:func:`coo_from_matlab`) or warm."""
    return jnp.asarray(ss.astype(np.float32))


@partial(jax.jit, static_argnames=("M", "N"))
def coo_to_dense(rows, cols, vals, *, M: int, N: int) -> jax.Array:
    """Dense scatter-add reference (duplicates sum — Matlab semantics)."""
    valid = rows < M
    dense = jnp.zeros((M, N), vals.dtype)
    return dense.at[
        jnp.where(valid, rows, 0), jnp.where(valid, cols, 0)
    ].add(jnp.where(valid, vals, 0.0))
