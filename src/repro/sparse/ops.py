"""repro.sparse.ops — one operator surface for every registered format.

Before this module each format grew its own ad-hoc methods (``CSC``
spmv in ``repro.core.csc``, a second spmv in ``repro.kernels.spmv``,
``ShardedCSC.spmv``, per-format ``to_dense``).  Here the operators are
dispatched *per registered format* through the same registry that
:func:`repro.sparse.convert` uses, so a consumer writes
``ops.matmul(A, x)`` for any ``A`` and new formats join by calling
:func:`register_op` — no format branching at call sites.

Every operator composes inside ``jit``/``grad``/``vmap``: ``matmul``
on CSC carries the sparse ``custom_vjp`` (``spmv`` VJP = ``spmv_t``),
assembly reaches here through the differentiable
:meth:`~repro.sparse.pattern.SparsePattern.assemble`, and the remaining
operators are built from gathers/segment-sums whose transposes are
already sparse.

    >>> import numpy as np
    >>> import jax, jax.numpy as jnp
    >>> from repro.sparse import fsparse, plan, ops

    ``fsparse`` gives a padded CSC; the operators work on it directly
    (duplicates at (1, 1) were summed at assembly):

    >>> A = fsparse([1, 2, 2, 1], [1, 1, 2, 1], [1.0, 2.0, 3.0, 4.0],
    ...             (2, 2))
    >>> np.asarray(ops.to_dense(A))
    array([[5., 0.],
           [2., 3.]], dtype=float32)
    >>> np.asarray(ops.matmul(A, jnp.ones(2, jnp.float32)))
    array([5., 5.], dtype=float32)

    A *sparse* second operand dispatches to the two-phase SpGEMM
    subsystem (:mod:`repro.sparse.spgemm`) — symbolic product plan
    cached across calls, O(flops) numeric refill:

    >>> np.asarray(ops.to_dense(ops.matmul(A, A)))
    array([[25.,  0.],
           [16.,  9.]], dtype=float32)
    >>> np.asarray(ops.diagonal(A))
    array([5., 3.], dtype=float32)

    ``transpose`` of a CSC is a free reinterpretation (a CSR sharing
    the same arrays), and back:

    >>> T = ops.transpose(A)
    >>> type(T).__name__, T.shape
    ('CSR', (2, 2))
    >>> np.asarray(ops.to_dense(T))
    array([[5., 2.],
           [0., 3.]], dtype=float32)

    ``add``/``scale`` stay in the input's format:

    >>> Z = ops.add(A, ops.scale(A, -1.0))
    >>> float(jnp.abs(ops.to_dense(Z)).max())
    0.0

    And the whole pipeline differentiates — the backward of the
    assembly fill is the O(L) gather-by-slot through the plan:

    >>> pat = plan(np.array([0, 1, 1]), np.array([0, 0, 1]), (2, 2))
    >>> loss = lambda v: ops.matmul(pat.assemble(v),
    ...                             jnp.ones(2, jnp.float32)).sum()
    >>> np.asarray(jax.grad(loss)(jnp.ones(3, jnp.float32)))
    array([1., 1., 1.], dtype=float32)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from ..core.coo import COO
from ..core.csc import CSC, slot_columns, spmv as _csc_spmv
from .formats import BSR, CSR, SymCSC, convert, format_of
from .pattern import fill_dtype

__all__ = [
    "add",
    "diagonal",
    "matmul",
    "register_op",
    "scale",
    "scatter_rows",
    "spmv_impl",
    "to_dense",
    "transpose",
]

# ---------------------------------------------------------------------------
# Per-format dispatch (rides on the format registry: names come from
# repro.sparse.formats.format_of, so registering a format there and an
# op here is all a new format needs)
# ---------------------------------------------------------------------------
_OP_IMPLS: Dict[Tuple[str, str], Callable] = {}


def register_op(op: str, fmt: str, fn: Callable) -> None:
    """Register ``fn`` as the ``op`` implementation for format ``fmt``."""
    _OP_IMPLS[(op, fmt)] = fn


def _dispatch(op: str, A, *, hub: str | None = None):
    """Implementation for ``(op, format_of(A))``, optionally via a hub.

    When no direct implementation exists and ``hub`` is given, ``A`` is
    converted through the format registry and the hub's implementation
    is used (the result is then in terms of the hub format — cheap for
    ``"coo"``, whose conversions never re-sort).
    """
    fmt = format_of(A)
    fn = _OP_IMPLS.get((op, fmt))
    if fn is not None:
        return fn, A
    if hub is not None and (op, hub) in _OP_IMPLS:
        return _OP_IMPLS[(op, hub)], convert(A, hub)
    raise TypeError(
        f"no {op!r} implementation for format {fmt!r} "
        f"(registered: {sorted(k for k in _OP_IMPLS if k[0] == op)})"
    )


# ---------------------------------------------------------------------------
# matmul — spmv / spmm
# ---------------------------------------------------------------------------
def _coo_spmv(A: COO, x: jax.Array) -> jax.Array:
    valid = A.rows < A.M
    contrib = jnp.where(valid, A.vals * x[jnp.where(valid, A.cols, 0)], 0.0)
    return jnp.zeros((A.M,), contrib.dtype).at[
        jnp.where(valid, A.rows, 0)
    ].add(contrib)


def _csr_spmv(A: CSR, x: jax.Array) -> jax.Array:
    rows = slot_columns(A.indptr, A.nzmax)  # row of each slot
    valid = A.indices < A.N
    contrib = jnp.where(
        valid, A.data * x[jnp.where(valid, A.indices, 0)], 0.0
    )
    return jax.ops.segment_sum(
        contrib, jnp.clip(rows, 0, A.M - 1), num_segments=A.M
    )


def _sharded_spmv(A, x: jax.Array) -> jax.Array:
    return A.spmv(x)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _spmv_sym_vjp(shape, diag, data, indices, indptr, x):
    """Fused both-triangles symmetric SpMV with an explicit sparse VJP.

    Symmetric SpMV is self-transpose, so ``∂L/∂x = A g`` reuses the
    *same* fused kernel (no spmv_t dual, no dense intermediate);
    ``∂L/∂data[s] = x[col_s]·g[row_s] + x[row_s]·g[col_s]`` (the stored
    upper entry appears in both triangles) and ``∂L/∂diag = x · g`` —
    all O(nzmax) gathers through the halved structure.
    """
    from ..kernels.spmv_sym.ops import spmv_sym

    return spmv_sym(diag, data, indices, indptr, x)


def _spmv_sym_fwd(shape, diag, data, indices, indptr, x):
    y = _spmv_sym_vjp(shape, diag, data, indices, indptr, x)
    return y, (diag, data, indices, indptr, x)


def _spmv_sym_bwd(shape, res, g):
    diag, data, indices, indptr, x = res
    M = int(shape[0])
    g_x = _spmv_sym_vjp(shape, diag, data, indices, indptr, g)
    g_diag = (x * g).astype(diag.dtype)
    cols = slot_columns(indptr, data.shape[-1])
    valid = indices < M
    r = jnp.where(valid, indices, 0)
    c = jnp.where(valid, jnp.clip(cols, 0, max(M - 1, 0)), 0)
    g_data = jnp.where(
        valid, x[c] * g[r] + x[r] * g[c], jnp.zeros((), data.dtype)
    ).astype(data.dtype)
    return (g_diag, g_data, None, None, g_x)


_spmv_sym_vjp.defvjp(_spmv_sym_fwd, _spmv_sym_bwd)


def _symcsc_spmv(A: SymCSC, x: jax.Array) -> jax.Array:
    return _spmv_sym_vjp(A.shape, A.diag, A.data, A.indices, A.indptr, x)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _spmv_bsr_vjp(shape, block, data, indices, indptr, x):
    """Blocked SpMV with a sparse VJP through the stored tiles.

    ``∂L/∂x`` scatter-adds ``data[k]ᵀ @ g_block[row_k]`` per stored
    block into block *columns* (the Aᵀ product without materializing a
    transpose) and ``∂L/∂data[k] = g_block[row_k] ⊗ x_block[col_k]`` —
    both O(nbmax · b²) like the forward.
    """
    from ..kernels.spmv_sym.ops import spmv_bsr

    return spmv_bsr(data, indices, indptr, x, shape=shape, block=block)


def _spmv_bsr_fwd(shape, block, data, indices, indptr, x):
    y = _spmv_bsr_vjp(shape, block, data, indices, indptr, x)
    return y, (data, indices, indptr, x)


def _spmv_bsr_bwd(shape, block, res, g):
    data, indices, indptr, x = res
    M, N = int(shape[0]), int(shape[1])
    b = int(block)
    Mb, Nb = M // b, N // b
    nbmax = data.shape[0]
    bcols = slot_columns(indptr, nbmax)
    valid = indices < Mb
    br = jnp.where(valid, indices, 0)
    bc = jnp.where(valid, jnp.clip(bcols, 0, max(Nb - 1, 0)), 0)
    gb = g.reshape(Mb, b)[br]                              # [nbmax, b]
    xb = x.reshape(Nb, b)[bc]                              # [nbmax, b]
    ok = valid[:, None]
    g_data = jnp.where(
        valid[:, None, None], jnp.einsum("ki,kj->kij", gb, xb), 0
    ).astype(data.dtype)
    contrib = jnp.where(ok, jnp.einsum("kij,ki->kj", data, gb), 0)
    g_x = jnp.zeros((Nb, b), contrib.dtype).at[bc].add(contrib)
    return (g_data, None, None, g_x.reshape(N).astype(x.dtype))


_spmv_bsr_vjp.defvjp(_spmv_bsr_fwd, _spmv_bsr_bwd)


def _bsr_spmv(A: BSR, x: jax.Array) -> jax.Array:
    return _spmv_bsr_vjp(A.shape, A.block, A.data, A.indices, A.indptr, x)


def _spgemm(A, B) -> CSC:
    """Sparse x sparse product through the two-phase SpGEMM subsystem.

    Both operands are converted to the CSC hub; the symbolic phase
    (:func:`repro.sparse.spgemm.product_plan`) is served from a
    host-side LRU keyed on both structures — the ``sparse2`` spirit —
    so repeated products with fixed sparsity (multigrid Galerkin
    operators, normal equations) pay only the O(flops) numeric refill.
    """
    from .spgemm import cached_product_plan

    Ac = convert(A, "csc")
    Bc = convert(B, "csc")
    return cached_product_plan(Ac, Bc).multiply(Ac.data, Bc.data)


def spmv_impl(A):
    """Resolve the per-format spmv implementation for ``A`` once.

    Returns ``(fn, A_resolved)`` — the registered implementation and
    the (possibly hub-converted) operand it applies to.  The serving
    AOT tier (:mod:`repro.sparse.serving`) uses this to bake the
    dispatch decision into a lowered executable at plan time instead of
    re-dispatching per request; ``fn(A_resolved, x)`` is exactly what
    :func:`matmul` would run for a dense vector ``x``.
    """
    return _dispatch("spmv", A, hub="csc")


def matmul(A, x) -> "jax.Array | CSC":
    """``A @ x`` (spmv), ``A @ X`` (spmm), or sparse ``A @ B`` (SpGEMM).

    Dense operands dispatch per registered format; the CSC path carries
    the sparse ``custom_vjp`` (backward for ``x`` is
    :func:`repro.core.csc.spmv_t`, backward for ``A.data`` a structure
    gather), so ``jax.grad`` through ``matmul(pat.assemble(vals), x)``
    never builds a dense intermediate.  A *sparse* second operand takes
    the two-phase SpGEMM path instead (plan-cached symbolic product +
    O(flops) refill — see :mod:`repro.sparse.spgemm`) and returns a
    padded :class:`CSC`, differentiable w.r.t. both operands' data.
    """
    try:
        fmt = format_of(x)
    except TypeError:
        fmt = None  # not a registered sparse format: dense spmv/spmm
    if fmt is not None:
        # outside the try: a TypeError raised *inside* the SpGEMM path
        # (e.g. no conversion path for A) must surface, not fall
        # through to the dense path with a misleading error
        return _spgemm(A, x)
    x = jnp.asarray(x)
    fn, A = _dispatch("spmv", A, hub="csc")
    with jax.named_scope("spmv"):
        if x.ndim == 1:
            return fn(A, x)
        if x.ndim == 2:
            return jax.vmap(lambda col: fn(A, col), in_axes=1,
                            out_axes=1)(x)
    raise ValueError(f"matmul expects a vector or matrix, got ndim={x.ndim}")


# ---------------------------------------------------------------------------
# transpose — CSC<->CSR are free reinterpretations of the same arrays
# ---------------------------------------------------------------------------
def _csc_transpose(A: CSC) -> CSR:
    # Aᵀ's rows are A's columns: the column pointer *is* the transposed
    # row pointer and the row indices *are* the transposed column
    # indices (sentinel M == the CSR col sentinel for shape (N, M)).
    return CSR(data=A.data, indices=A.indices, indptr=A.indptr,
               nnz=A.nnz, shape=(A.N, A.M))


def _csr_transpose(A: CSR) -> CSC:
    return CSC(data=A.data, indices=A.indices, indptr=A.indptr,
               nnz=A.nnz, shape=(A.N, A.M))


def _coo_transpose(A: COO) -> COO:
    valid = A.rows < A.M
    return COO(
        rows=jnp.where(valid, A.cols, A.N).astype(jnp.int32),
        cols=jnp.where(valid, A.rows, 0).astype(jnp.int32),
        vals=A.vals,
        shape=(A.N, A.M),
    )


def _symcsc_transpose(A: SymCSC) -> SymCSC:
    # A == Aᵀ by construction: the transpose is the SAME object (epoch,
    # structure identity and any caches keyed on it are preserved).
    return A


def _bsr_transpose(A: BSR) -> BSR:
    """Direct BSR transpose: one stable block sort + per-tile swap.

    The same single-stable-sort argument as ``_resort_compressed``:
    the stored block stream is (block-col, block-row) lexicographic, so
    one stable argsort by block row yields the transposed order; each
    dense tile transposes in registers.  Zeroed invalid tails make the
    double transpose bit-identical.
    """
    b, Mb, Nb = A.block, A.Mb, A.Nb
    bcols = slot_columns(A.indptr, A.nbmax)
    valid = A.indices < Mb
    order = jnp.argsort(A.indices, stable=True)   # sentinels sink last
    counts = jnp.bincount(
        jnp.where(valid, A.indices, Mb), length=Mb + 1
    )[:Mb].astype(jnp.int32)
    indptr = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts).astype(jnp.int32)]
    )
    data = jnp.where(
        valid[:, None, None], jnp.swapaxes(A.data, 1, 2), 0.0
    )[order]
    indices = jnp.where(
        valid, jnp.clip(bcols, 0, max(Nb - 1, 0)), Nb
    )[order].astype(jnp.int32)
    return BSR(data=data, indices=indices, indptr=indptr, nnz=A.nnz,
               shape=(A.N, A.M), block=b)


def transpose(A):
    """``Aᵀ``.  CSC <-> CSR is a zero-cost array reinterpretation;
    COO swaps its index vectors; SymCSC returns the same object
    (``A == Aᵀ``); BSR resorts its block stream directly;
    block-partitioned formats fall back to the COO hub (a block-row
    partition has no block-col dual)."""
    fn, A = _dispatch("transpose", A, hub="coo")
    return fn(A)


# ---------------------------------------------------------------------------
# add / scale / diagonal / to_dense
# ---------------------------------------------------------------------------
def add(A, B):
    """``A + B`` for any two registered formats of equal shape.

    Concatenates the COO triplet streams and reassembles into ``A``'s
    format — one plan over L_A + L_B triplets; overlapping structure
    merges by the duplicate-summing rule of assembly.  The re-plan's
    fill follows the shared :func:`~repro.sparse.pattern.fill_dtype`
    contract: integer operands promote once to f32 (a fill never emits
    an int-typed matrix) and 16-bit floats keep their dtype while
    accumulating duplicates in f32.
    """
    if tuple(A.shape) != tuple(B.shape):
        raise ValueError(f"shape mismatch: {A.shape} vs {B.shape}")
    ca, cb = convert(A, "coo"), convert(B, "coo")
    dtype = fill_dtype(jnp.promote_types(ca.vals.dtype, cb.vals.dtype))
    out = COO(
        rows=jnp.concatenate([ca.rows, cb.rows]),
        cols=jnp.concatenate([ca.cols, cb.cols]),
        vals=jnp.concatenate(
            [ca.vals.astype(dtype), cb.vals.astype(dtype)]
        ),
        shape=tuple(A.shape),
    )
    fmt = format_of(A)
    if fmt == "coo":
        return out
    kwargs = {"mesh": A.mesh} if fmt == "sharded" else {}
    if fmt == "bsr":
        kwargs = {"block": A.block}
    return convert(out, fmt, **kwargs)


def scale(A, alpha):
    """``alpha * A`` — elementwise scale of the stored values, format
    and structure preserved.  SymCSC scales both of its numeric
    streams (dense diagonal + strict upper)."""
    if isinstance(A, SymCSC):
        return dataclasses.replace(
            A, diag=A.diag * alpha, data=A.data * alpha
        )
    field = "vals" if isinstance(A, COO) else "data"
    return dataclasses.replace(
        A, **{field: getattr(A, field) * alpha}
    )


def _symcsc_diagonal(A: SymCSC) -> jax.Array:
    # the dense diagonal is stored outright — zero work
    return A.diag


def _coo_diagonal(A: COO) -> jax.Array:
    k = min(A.M, A.N)
    valid = jnp.logical_and(A.rows < A.M, A.rows == A.cols)
    return (
        jnp.zeros((k,), A.vals.dtype)
        .at[jnp.where(valid, A.rows, k)]
        .add(jnp.where(valid, A.vals, 0.0), mode="drop")
    )


def diagonal(A) -> jax.Array:
    """Main diagonal as a dense ``min(M, N)`` vector (duplicates sum)."""
    fn, A = _dispatch("diagonal", A, hub="coo")
    return fn(A)


def to_dense(A) -> jax.Array:
    """Dense materialization — the universal (expensive) escape hatch."""
    return A.to_dense()


# ---------------------------------------------------------------------------
# scatter_rows — the shared dispatch/combine primitive
# ---------------------------------------------------------------------------
@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scatter_rows(num_slots, slot, rows):
    return (
        jnp.zeros((num_slots,) + rows.shape[1:], rows.dtype)
        .at[slot]
        .set(rows, mode="drop")
    )


def _scatter_rows_fwd(num_slots, slot, rows):
    return _scatter_rows(num_slots, slot, rows), slot


def _scatter_rows_bwd(num_slots, slot, g):
    keep = slot < num_slots
    keep = keep.reshape(keep.shape + (1,) * (g.ndim - 1))
    g_rows = jnp.where(
        keep, g[jnp.clip(slot, 0, num_slots - 1)], jnp.zeros((), g.dtype)
    )
    return (None, g_rows)


_scatter_rows.defvjp(_scatter_rows_fwd, _scatter_rows_bwd)


def scatter_rows(slot: jax.Array, rows: jax.Array, *, num_slots: int
                 ) -> jax.Array:
    """Collision-free row scatter with a gather backward.

    ``out[slot[k]] = rows[k]`` for ``slot[k] < num_slots`` (out-of-range
    slots — capacity overflow sentinels — are dropped); slots must be
    unique, which every fsparse-style placement guarantees by
    construction.  The ``custom_vjp`` backward is the masked gather
    ``g_rows[k] = g[slot[k]]`` — the same irank-replay the paper uses
    for its combine step.  This is the primitive behind the MoE
    dispatch/combine path and the embedding-gradient assembly in
    :mod:`repro.train.sparse_grads`.
    """
    return _scatter_rows(num_slots, slot, rows)


# ---------------------------------------------------------------------------
# Built-in registrations
# ---------------------------------------------------------------------------
register_op("spmv", "csc", _csc_spmv)
register_op("spmv", "csr", _csr_spmv)
register_op("spmv", "coo", _coo_spmv)
register_op("spmv", "sharded", _sharded_spmv)
register_op("spmv", "symcsc", _symcsc_spmv)
register_op("spmv", "bsr", _bsr_spmv)
register_op("transpose", "csc", _csc_transpose)
register_op("transpose", "csr", _csr_transpose)
register_op("transpose", "coo", _coo_transpose)
register_op("transpose", "symcsc", _symcsc_transpose)
register_op("transpose", "bsr", _bsr_transpose)
register_op("diagonal", "coo", _coo_diagonal)
register_op("diagonal", "symcsc", _symcsc_diagonal)
