"""The comparison that decides ``correct``.

What the timed path returned (``nnz``, ``indptr``, ``indices`` and
``data`` of a CSC) against the plain reference (:mod:`bench.oracle`):

* ``structure_mismatches``: entries of ``indptr`` and ``indices[:nnz]``
  that differ, plus the difference in ``nnz``.  Exact: limit 0.
* ``data_rel_err``: the widest gap between a stored value and the
  reference's float64 sum, each measured against the sum of the
  magnitudes of the triplets that entry adds up (the scale of the
  rounding error of any order of summation).

The control (:func:`bf16_data`) is the reference computed in bfloat16,
the precision below the float32 that the configurations state, put in
the program's place.
"""
from __future__ import annotations

import numpy as np


def compare(got: dict, ref, ss) -> dict:
    """Numbers of one result ``got`` (host arrays ``nnz``, ``indptr``,
    ``indices``, ``data``) against ``ref`` (a
    :class:`bench.oracle.StructureReference`) for values ``ss``."""
    nnz = int(got["nnz"])
    n = min(nnz, ref.nnz)
    indptr = np.asarray(got["indptr"])
    mism = abs(nnz - ref.nnz)
    if indptr.shape != ref.indptr.shape:
        mism += max(indptr.size, ref.indptr.size)
    else:
        mism += int(np.count_nonzero(indptr != ref.indptr))
    mism += int(np.count_nonzero(
        np.asarray(got["indices"])[:n] != ref.indices[:n]))
    data = np.asarray(got["data"])[:n].astype(np.float64)
    want = ref.values(ss)[:n]
    scale = ref.magnitudes(ss)[:n]
    gap = np.abs(data - want)
    rel = np.where(scale > 0, gap / np.where(scale > 0, scale, 1.0), gap)
    return {"structure_mismatches": mism,
            "data_rel_err": float(rel.max()) if n else 0.0}


def bf16_data(ref, ss) -> np.ndarray:
    """The reference's ``data`` computed in bfloat16: values rounded to
    bfloat16, summed, and the sums rounded to bfloat16."""
    from ml_dtypes import bfloat16

    low = np.asarray(ss, np.float64).astype(bfloat16).astype(np.float64)
    return ref.values(low).astype(bfloat16).astype(np.float32)


def worst(readings: list) -> dict:
    """The largest of each number over several results."""
    out: dict = {}
    for r in readings:
        for k, v in r.items():
            out[k] = max(out.get(k, v), v)
    return out


def verdict(numbers: dict, limits: dict) -> tuple:
    """``(correct, {name: {"value", "limit"}})``: every number at or
    under its limit."""
    table = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(numbers[k] <= limits[k] for k in limits)
    return ok, table
