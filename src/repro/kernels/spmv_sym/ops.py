"""Dispatchers for the symmetric / blocked SpMV kernel family.

Backend policy: ``interpret=None`` follows the ``spmv_sym`` tuning
policy's ``method`` knob.  Its prior is ``"ref"`` on every backend —
the jnp oracles in :mod:`.ref` — because the Pallas kernels gather
from a 1-D VMEM-resident vector, which the TPU compiler does not lower
("Only 2D gather is supported").  ``method="pallas"`` (or an explicit
``interpret=True``/``False``) runs the kernels, guarded by the shared
8 MB residency cap; the interpreter cross-validates them in tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.csc import slot_columns
from ...sparse import tuning
from .ref import spmv_bsr_ref, spmv_sym_ref
from .spmv_sym import bsr_tiles, sym_streams

#: deprecated alias of the registry-owned residency budget — this
#: family used to import the cap from ``segment_sum.ops``; a rebound
#: value overrides the resolved policy (see :func:`_budget`).
FUSED_RESIDENT_MAX_BYTES = tuning.RESIDENT_BUDGET_BYTES


def _budget(M: int, dtype) -> int:
    """Resolved residency budget of one symmetric/blocked SpMV call."""
    pol = tuning.resolve_policy("spmv_sym", M=M, dtype=dtype)
    if FUSED_RESIDENT_MAX_BYTES != tuning.RESIDENT_BUDGET_BYTES:
        return int(FUSED_RESIDENT_MAX_BYTES)
    return int(pol["resident_max_bytes"])


def _use_kernel(resident_bytes: int, budget: int, method: str,
                interpret: bool | None) -> bool:
    if resident_bytes > budget:
        return False
    if interpret is None:
        return method == "pallas"     # the policy decides
    return True                       # explicit True/False: run Pallas


def sym_vmem_spec(M: int, dtype=jnp.float32) -> dict:
    """Static residency decision of the symmetric SpMV kernel.

    Mirrors :func:`spmv_sym`'s runtime guard: the dense vector ``x``
    (``M`` elements) stays VMEM-resident so both triangle contributions
    read it in one sweep.  Off-TPU the jnp oracle runs regardless of
    the budget; ``path`` reports the budget decision alone.
    """
    resident = int(M) * jnp.dtype(dtype).itemsize
    budget = _budget(int(M), dtype)
    fits = resident <= budget
    return {
        "family": "spmv_sym",
        "params": {"M": int(M), "dtype": jnp.dtype(dtype).name},
        "resident_bytes": resident,
        "budget_bytes": budget,
        "fits": fits,
        "path": "pallas-sym-streams" if fits else "xla-ref",
    }


def bsr_vmem_spec(N: int, block: int, dtype=jnp.float32) -> dict:
    """Static residency decision of the blocked SpMV kernel.

    Mirrors :func:`spmv_bsr`'s runtime guard: the dense vector reshaped
    to ``(N // block, block)`` tiles stays VMEM-resident.
    """
    b = int(block)
    resident = (int(N) // b) * b * jnp.dtype(dtype).itemsize if b else 0
    budget = _budget(int(N), dtype)
    fits = resident <= budget
    return {
        "family": "spmv_bsr",
        "params": {"N": int(N), "block": b,
                   "dtype": jnp.dtype(dtype).name},
        "resident_bytes": resident,
        "budget_bytes": budget,
        "fits": fits,
        "path": "pallas-bsr-tiles" if fits else "xla-ref",
    }


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def spmv_sym(diag, data, indices, indptr, x, *, block_b: int | None = None,
             interpret: bool | None = None) -> jax.Array:
    """Fused both-triangles symmetric SpMV over strict-upper storage.

    One sweep of the halved stream accumulates ``y[i] += a * x[j]`` and
    ``y[j] += a * x[i]`` per stored upper entry (plus the dense
    diagonal) — see :func:`.ref.spmv_sym_ref` for the exact semantics;
    this wrapper only chooses between the Pallas kernel and the oracle.
    """
    M = diag.shape[0]
    nzmax = data.shape[-1]
    pol = tuning.resolve_policy("spmv_sym", M=M, L=nzmax, dtype=x.dtype)
    if block_b is None:
        block_b = int(pol["block_b"])
    budget = _budget(M, x.dtype)
    if M == 0 or nzmax == 0 or not _use_kernel(
            x.nbytes, budget, pol["method"], interpret):
        return spmv_sym_ref(diag, data, indices, indptr, x)
    cols = jnp.clip(slot_columns(indptr, nzmax), 0, M - 1)
    up, cs = sym_streams(indices, cols, data, x, M=M, block_b=block_b,
                         interpret=interpret)
    y = diag.astype(data.dtype) * x
    y = y.at[jnp.where(indices < M, indices, 0)].add(up)
    csum = jnp.concatenate([jnp.zeros((1,), cs.dtype), cs])
    return y + (csum[indptr[1:]] - csum[indptr[:-1]])


@functools.partial(jax.jit,
                   static_argnames=("shape", "block", "block_t", "interpret"))
def spmv_bsr(data, indices, indptr, x, *, shape, block: int,
             block_t: int | None = None,
             interpret: bool | None = None) -> jax.Array:
    """Blocked SpMV: dense ``b x b`` register tiles over block-CSC."""
    M, N = shape
    b = int(block)
    nbmax = data.shape[0]
    pol = tuning.resolve_policy("spmv_sym", M=M, N=N, dtype=x.dtype)
    if block_t is None:
        block_t = int(pol["block_t"])
    resident = (N // b) * b * x.dtype.itemsize if b else 0
    if M == 0 or nbmax == 0 or b == 0 or not _use_kernel(
            resident, _budget(N, x.dtype), pol["method"], interpret):
        return spmv_bsr_ref(data, indices, indptr, x, shape=shape,
                            block=block)
    Mb, Nb = M // b, N // b
    bcols = jnp.clip(slot_columns(indptr, nbmax), 0, max(Nb - 1, 0))
    dtype = jnp.result_type(data, x)
    tiles = bsr_tiles(indices, bcols, data.astype(dtype),
                      x.astype(dtype).reshape(Nb, b), Mb=Mb,
                      block_t=block_t, interpret=interpret)
    y = jnp.zeros((Mb, b), dtype).at[
        jnp.where(indices < Mb, indices, 0)
    ].add(tiles)
    return y.reshape(M)
