"""Two-phase sparse x sparse products (SpGEMM) on the plan/fill core.

A sparse product ``C = A @ B`` *is* an assembly problem: expanding
every stored ``B(k, j)`` against the stored column ``A(:, k)`` yields
the raw triplet stream ``(i, j, A(i, k) * B(k, j))``, and summing its
duplicates is exactly the Matlab ``sparse`` contract the paper's
pipeline implements.  So the expensive half of SpGEMM — where does
each partial product land? — is the symbolic phase the repo already
has, and the product inherits the paper's §2.3 split:

``product_plan(pat_A, pat_B)`` runs once per structure pair:

  1. per-entry expansion counts off ``indptr`` gathers (host-side
     numpy over the concrete structure arrays — like ``sparse2``'s
     plan cache, the symbolic phase lives outside ``jit``),
  2. a static expansion capacity ``flops_max`` (= the classic SpGEMM
     flop count; optionally padded to a caller-fixed capacity),
  3. an ordinary :func:`repro.sparse.plan` over the expanded
     ``(i, j)`` stream — reusing the radix planner and every other
     registered ``method=`` unchanged.

The returned :class:`ProductPattern` stores the *sorted-order*
expansion maps ``sa``/``sb`` (which stored slot of A and of B feeds
the k-th element of the sorted product stream), so
:meth:`ProductPattern.multiply` is the O(flops) numeric phase —
gather-multiply-scatter, no sorting — and is differentiable w.r.t.
BOTH operands via the same ``custom_vjp`` gather-by-slot trick as the
assembly fills: the backward is a padding-masked gather of the output
cotangent through the stored plan plus one scatter-add per operand
through the stored expansion maps.  No re-sort, no dense intermediate.

This is the fixed-structure product workload of FEM multigrid (the
Galerkin triple product ``P' * A * P`` — the pattern is fixed across
solver iterations, only values change; see
``examples/fem_multigrid.py``), graph contraction, and normal
equations ``A' * A``.

    >>> import numpy as np
    >>> import jax.numpy as jnp
    >>> from repro.sparse import plan, product_plan

    A = [[1, 2], [0, 3]] and B = [[4, 0], [5, 6]] as CSC plans +
    fills (structure once, values per call):

    >>> pa = plan(np.array([0, 0, 1]), np.array([0, 1, 1]), (2, 2))
    >>> pb = plan(np.array([0, 1, 1]), np.array([0, 0, 1]), (2, 2))
    >>> A = pa.assemble(jnp.array([1.0, 2.0, 3.0]))
    >>> B = pb.assemble(jnp.array([4.0, 5.0, 6.0]))

    The symbolic product phase runs once per structure pair; the
    numeric refill is O(flops) and reusable for any operand values
    sharing the structures:

    >>> pp = product_plan(pa, pb)
    >>> int(pp.flops), int(pp.pattern.nnz)   # 5 partial products, 4 cells
    (5, 4)
    >>> C = pp.multiply(A.data, B.data)
    >>> np.asarray(C.to_dense())
    array([[14., 12.],
           [15., 18.]], dtype=float32)
    >>> A2 = pa.assemble(jnp.array([1.0, 0.0, 1.0]))   # new values,
    >>> np.asarray(pp.multiply(A2.data, B.data).to_dense())  # same plan
    array([[4., 0.],
           [5., 6.]], dtype=float32)
"""
from __future__ import annotations

import dataclasses
import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..core.csc import CSC
from .formats import CSR
from .lru import LRUCache
from .pattern import (
    SparsePattern,
    accum_dtype,
    fill_dtype,
    plan,
    trivial_pattern,
)

__all__ = [
    "ProductPattern",
    "product_plan",
    "product_lookup",
    "cached_product_plan",
    "product_cache_clear",
    "product_cache_info",
    "retire_structure",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ProductPattern:
    """Symbolic SpGEMM plan: C's assembly pattern + expansion maps.

    ``sa``/``sb`` are aligned with the *sorted* product stream (the
    order of ``pattern.slot``), so the numeric phase needs no extra
    permutation gather: element k of the sorted stream is
    ``data_A[sa[k]] * data_B[sb[k]]`` and lands in ``pattern.slot[k]``.
    Dropped expansion entries (capacity padding) carry the plan's
    ``slot == nzmax`` sentinel and ``sa == sb == 0`` placeholders.
    """

    sa: jax.Array        # int32[flops_max]; stored slot in A.data
    sb: jax.Array        # int32[flops_max]; stored slot in B.data
    pattern: SparsePattern  # C's plan over the expanded (i, j) stream
    a_capacity: int = dataclasses.field(metadata=dict(static=True))
    b_capacity: int = dataclasses.field(metadata=dict(static=True))
    #: static structure-version stamp, derived from the operand plans'
    #: ``epoch`` fields at planning time: a product planned against a
    #: since-updated operand carries a stale epoch, and jitted consumers
    #: retrace exactly once when the re-planned product replaces it.
    epoch: int = dataclasses.field(default=0, metadata=dict(static=True))

    # -- static geometry --------------------------------------------------
    @property
    def flops(self) -> int:
        """Static expansion capacity (the classic SpGEMM flop count)."""
        return int(self.sa.shape[-1])

    @property
    def shape(self) -> tuple[int, int]:
        return self.pattern.shape

    @property
    def nzmax(self) -> int:
        return self.pattern.nzmax

    # -- numeric phase ----------------------------------------------------
    def multiply(self, data_A: jax.Array, data_B: jax.Array) -> CSC:
        """O(flops) numeric refill: gather-multiply-scatter, no sort.

        ``data_A``/``data_B`` are the ``data`` vectors of CSC matrices
        sharing the structures this plan was built from (padded tails
        included — their zeros never reach a kept slot).  The result is
        C as a padded :class:`CSC`.  Differentiable w.r.t. both
        operands: the ``custom_vjp`` backward is the masked
        gather-by-slot of the cotangent through the stored plan plus
        one scatter-add per operand through ``sa``/``sb``.
        """
        data_A = jnp.asarray(data_A)
        data_B = jnp.asarray(data_B)
        if data_A.ndim != 1 or data_A.shape[0] != self.a_capacity:
            raise ValueError(
                f"data_A has shape {data_A.shape} but this product was "
                f"planned for an A with nzmax={self.a_capacity}"
            )
        if data_B.ndim != 1 or data_B.shape[0] != self.b_capacity:
            raise ValueError(
                f"data_B has shape {data_B.shape} but this product was "
                f"planned for a B with nzmax={self.b_capacity}"
            )
        with jax.named_scope("multiply"):
            data = _multiply_vjp(
                self.nzmax, self.sa, self.sb, self.pattern.slot,
                data_A, data_B,
            )
        return CSC(
            data=data,
            indices=self.pattern.indices,
            indptr=self.pattern.indptr,
            nnz=self.pattern.nnz,
            shape=self.pattern.shape,
        )


def _product_scatter(nzmax: int, sa, sb, slot, va, vb):
    """Forward numeric phase: expansion products scatter-reduced.

    Dropped expansion entries carry the ``slot == nzmax`` sentinel, so
    one ``mode="drop"`` scatter discards them — same convention as
    :meth:`SparsePattern.scatter`.  16-bit products accumulate in f32
    (the shared :func:`accum_dtype` rule).
    """
    dtype = fill_dtype(jnp.promote_types(va.dtype, vb.dtype))
    acc = accum_dtype(dtype)
    v = va.astype(acc)[sa] * vb.astype(acc)[sb]
    return (
        jnp.zeros((nzmax,), acc)
        .at[slot]
        .add(v, mode="drop")
        .astype(dtype)
    )


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _multiply_vjp(nzmax: int, sa, sb, slot, va, vb):
    """Differentiable numeric phase (forward == :func:`_product_scatter`).

    ``data[s] = Σ_k va[sa[k]] · vb[sb[k]]`` over the kept expansion
    entries landing in slot ``s``, so the backward w.r.t. each operand
    is the product rule through the stored maps:

        g_va[a] = Σ_{k: sa[k]=a} g[slot[k]] · vb[sb[k]]
        g_vb[b] = Σ_{k: sb[k]=b} g[slot[k]] · va[sa[k]]

    — one O(flops) padding-masked gather-by-slot of ``g`` plus one
    gather + scatter-add per operand.  No re-sort, no XLA
    transpose-of-scatter, no dense intermediate.
    """
    return _product_scatter(nzmax, sa, sb, slot, va, vb)


def _multiply_vjp_fwd(nzmax, sa, sb, slot, va, vb):
    out = _product_scatter(nzmax, sa, sb, slot, va, vb)
    return out, (sa, sb, slot, va, vb)


def _multiply_vjp_bwd(nzmax, res, g):
    sa, sb, slot, va, vb = res
    acc = accum_dtype(g.dtype)
    valid = slot < nzmax
    g_s = jnp.where(
        valid, g[jnp.clip(slot, 0, nzmax - 1)].astype(acc),
        jnp.zeros((), acc),
    )
    g_va = (
        jnp.zeros((va.shape[0],), acc)
        .at[sa]
        .add(g_s * vb.astype(acc)[sb])
        .astype(va.dtype)
    )
    g_vb = (
        jnp.zeros((vb.shape[0],), acc)
        .at[sb]
        .add(g_s * va.astype(acc)[sa])
        .astype(vb.dtype)
    )
    return (None, None, None, g_va, g_vb)


_multiply_vjp.defvjp(_multiply_vjp_fwd, _multiply_vjp_bwd)


def _csc_structure(S) -> tuple[np.ndarray, np.ndarray, int, int]:
    """Concrete (indices, indptr, nnz, nzmax) of a plan or CSC matrix.

    Accepts anything *column*-compressed: a :class:`SparsePattern` or a
    :class:`CSC` (the structure fields coincide by design).  A
    row-compressed operand (CSR) would pass an attribute check and
    silently produce a wrong product, so the compression axis is
    validated against the shape — ``indptr`` must span the columns.
    The arrays must be concrete — the symbolic phase is host-side,
    like the ``sparse2`` plan cache.
    """
    for f in ("indices", "indptr"):
        if not hasattr(S, f):
            raise TypeError(
                f"product_plan operands must be column-compressed "
                f"(SparsePattern or CSC) — {type(S).__name__} has no "
                f"{f!r}; convert(A, 'csc') first"
            )
    if isinstance(S, CSR):
        # a square CSR would pass the indptr-length check below and
        # silently compute the product of the transpose
        raise TypeError(
            "product_plan operands must be column-compressed; got a "
            "CSR — convert(A, 'csc') first"
        )
    indptr = np.asarray(S.indptr)
    if indptr.shape[0] != int(S.shape[1]) + 1:
        raise TypeError(
            f"product_plan operands must be column-compressed, but this "
            f"{type(S).__name__} of shape {tuple(S.shape)} has an "
            f"indptr of length {indptr.shape[0]} (expected N+1 = "
            f"{int(S.shape[1]) + 1}); convert(A, 'csc') first"
        )
    indices = np.asarray(S.indices)
    return indices, indptr, int(np.asarray(S.nnz)), int(indices.shape[0])


def product_plan(
    A,
    B,
    *,
    method: str | None = None,
    nzmax: int | None = None,
    flops_max: int | None = None,
) -> ProductPattern:
    """Symbolic SpGEMM phase: expansion maps + C's assembly plan, once.

    ``A`` (M x K) and ``B`` (K x N) are column-compressed structures
    (:class:`SparsePattern` or :class:`CSC`; values are ignored — the
    product *pattern* is value-independent).  Per stored entry
    ``B(k, j)`` the stored column ``A(:, k)`` is expanded via
    ``indptr`` gathers into the raw product stream ``(i, j)``; an
    ordinary :func:`plan` over that stream (any registered ``method=``,
    radix included) does the hard half.  ``flops_max`` fixes the static
    expansion capacity (default: the exact flop count; larger values
    pad with dropped entries so one :class:`ProductPattern` shape can
    be reused across structure pairs); ``nzmax`` is C's storage
    capacity (default: the true structural nnz, known host-side after
    planning — the pattern is compacted by pure slicing, no re-plan).

    The result is reusable for any number of
    :meth:`ProductPattern.multiply` calls with different operand
    values — the repeated-product workload (multigrid Galerkin
    operators, normal equations) pays the symbolic phase once.
    """
    ir_A, jc_A, nnz_A, cap_A = _csc_structure(A)
    ir_B, jc_B, nnz_B, cap_B = _csc_structure(B)
    M, K = int(A.shape[0]), int(A.shape[1])
    Kb, N = int(B.shape[0]), int(B.shape[1])
    if K != Kb:
        raise ValueError(
            f"inner dimensions must agree: A is {A.shape}, B is {B.shape}"
        )
    # -- expansion: every stored B(k, j) against stored column A(:, k) --
    b_slots = np.arange(nnz_B, dtype=np.int64)
    k_of_b = ir_B[:nnz_B].astype(np.int64)          # B's row == A's col
    j_of_b = (
        np.searchsorted(jc_B, b_slots, side="right") - 1
    )                                               # B's col per slot
    col_start = jc_A[:-1].astype(np.int64)[k_of_b]
    col_len = (jc_A[1:] - jc_A[:-1]).astype(np.int64)[k_of_b]
    offsets = np.concatenate([[0], np.cumsum(col_len)])
    flops = int(offsets[-1])
    if flops_max is None:
        flops_max = flops
    elif flops_max < flops:
        raise ValueError(
            f"flops_max={flops_max} cannot hold the {flops} partial "
            "products of this structure pair"
        )
    # source maps + expanded (i, j) stream, in expansion order
    t_of_e = np.repeat(b_slots, col_len)            # B slot per product
    r_in_col = np.arange(flops, dtype=np.int64) - offsets[t_of_e]
    sa_e = col_start[t_of_e] + r_in_col             # A slot per product
    rows_C = np.full(flops_max, M, np.int32)        # padding: sentinel
    cols_C = np.zeros(flops_max, np.int32)
    rows_C[:flops] = ir_A[sa_e]
    cols_C[:flops] = j_of_b[t_of_e]
    sa = np.zeros(flops_max, np.int32)
    sb = np.zeros(flops_max, np.int32)
    sa[:flops] = sa_e
    sb[:flops] = t_of_e
    # -- the hard half: an ordinary plan over the expanded stream --------
    if flops_max == 0 or M == 0 or N == 0:
        pat = trivial_pattern(flops_max, (M, N),
                              nzmax=0 if nzmax is None else nzmax)
    else:
        pat = plan(
            jnp.asarray(rows_C), jnp.asarray(cols_C), (M, N),
            nzmax=flops_max if nzmax is None else nzmax, method=method,
        )
        if nzmax is None:
            # compact C's capacity to the true structural nnz (known
            # host-side now): every downstream O(nzmax) consumer —
            # multiply's scatter, spmv over C, chained products —
            # would otherwise scan flops_max slots.  Kept slots are
            # already 0..nnz-1 by construction, so this is slicing:
            # only the drop sentinel moves.
            nnz = int(np.asarray(pat.nnz))
            pat = dataclasses.replace(
                pat,
                slot=jnp.minimum(pat.slot, jnp.int32(nnz)),
                indices=pat.indices[:nnz],
            )
    # re-order the source maps into the sorted product stream once, so
    # the numeric phase needs no permutation gather of its own
    perm = np.asarray(pat.perm)
    return ProductPattern(
        sa=jnp.asarray(sa[perm]),
        sb=jnp.asarray(sb[perm]),
        pattern=pat,
        a_capacity=cap_A,
        b_capacity=cap_B,
        epoch=int(getattr(A, "epoch", 0)) + int(getattr(B, "epoch", 0)),
    )


# ---------------------------------------------------------------------------
# Product-plan cache (the sparse2 spirit for repeated products)
# ---------------------------------------------------------------------------
#: thread-safe SpGEMM plan LRU (shared core: repro.sparse.lru).
#: Capacity is read from REPRO_PRODUCT_CACHE_SIZE at import; resize at
#: runtime with ``_PRODUCT_CACHE.resize(n)``.
_PRODUCT_CACHE = LRUCache(16, name="product-plan",
                          env="REPRO_PRODUCT_CACHE_SIZE")


def _structure_key(S) -> tuple:
    """Structure-identity key of one column-compressed operand.

    Like the ``sparse2`` cache key: raw bytes alone are not an
    identity, so the shapes and dtypes participate too.
    """
    indices = np.asarray(S.indices)
    indptr = np.asarray(S.indptr)
    return (
        indices.tobytes(), indptr.tobytes(),
        indices.shape, indices.dtype.str, tuple(S.shape),
    )


#: operand structure keys retired by delta updates
#: (``SparsePattern.update`` through the ``plan_update`` facade).
#: Dependent cached products are dropped *lazily* — at the next
#: ``product_lookup`` — instead of eagerly walking the cache per update:
#: a churning structure that is never multiplied again costs nothing,
#: and a stale :class:`ProductPattern` can never be served because every
#: lookup purges first.
_RETIRED_STRUCTURES: set = set()
_RETIRED_LOCK = threading.Lock()


def retire_structure(structure_key: tuple) -> None:
    """Mark one operand structure (a :func:`_structure_key` token) stale.

    Called by the delta-update facade when a plan's structure is
    rewritten in place; cached products that consumed the old structure
    are dropped at the next lookup so they cannot leak or be served
    stale.
    """
    with _RETIRED_LOCK:
        _RETIRED_STRUCTURES.add(structure_key)


def _purge_retired() -> int:
    """Drop cached products whose operands were retired; returns count."""
    with _RETIRED_LOCK:
        if not _RETIRED_STRUCTURES:
            return 0
        retired = frozenset(_RETIRED_STRUCTURES)
        _RETIRED_STRUCTURES.clear()
    return _PRODUCT_CACHE.purge(
        lambda key: key[0] in retired or key[1] in retired
    )


def product_lookup(
    A, B, *, method: str | None = None, nzmax: int | None = None,
    flops_max: int | None = None,
) -> tuple:
    """Cache key + LRU-served :class:`ProductPattern` for one pair.

    The shared symbolic phase behind :func:`cached_product_plan` and
    the serving layer (which needs the key to persist the entry); the
    LRU is thread-safe and concurrent misses on different pairs plan in
    parallel.  Products whose operand structures were retired by a
    delta update (:func:`retire_structure`) are purged before the
    lookup, so a rewritten structure re-plans instead of serving the
    stale expansion maps.
    """
    _purge_retired()
    key = (_structure_key(A), _structure_key(B), method, nzmax, flops_max)
    pp = _PRODUCT_CACHE.get_or_create(
        key,
        lambda: product_plan(
            A, B, method=method, nzmax=nzmax, flops_max=flops_max
        ),
    )
    return key, pp


def cached_product_plan(
    A, B, *, method: str | None = None, nzmax: int | None = None,
    flops_max: int | None = None,
) -> ProductPattern:
    """``product_plan`` with a host-side LRU keyed on both structures.

    Repeated products over the same structure pair (the multigrid /
    normal-equations workload, and ``ops.matmul`` on two sparse
    operands) skip the symbolic phase entirely and pay only the
    O(flops) :meth:`ProductPattern.multiply`.
    """
    return product_lookup(
        A, B, method=method, nzmax=nzmax, flops_max=flops_max
    )[1]


def product_cache_info() -> dict:
    """Introspection for tests/ops: product plan-cache state.

    The historical ``size``/``capacity`` keys are kept; ``hits``/
    ``misses``/``evictions``/``insertions`` are the serving metrics of
    the shared locked LRU.
    """
    return _PRODUCT_CACHE.info()


def product_cache_clear() -> None:
    _PRODUCT_CACHE.clear()
