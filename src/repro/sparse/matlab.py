"""Matlab-compatibility facade over the two-phase core.

Thin wrappers with Matlab ``sparse``/fsparse semantics (unit-offset
indices, duplicate summing, the paper's §2.1 index-expansion extension),
all implemented on :func:`repro.sparse.plan` + ``SparsePattern``:

  fsparse(i, j, s, [shape], [nzmax], method=...)   one-shot assembly
  sparse2(i, j, s, ...)                            assembly with a
      host-side cache of hot symbolic plans — repeated calls with the
      same index vectors skip Parts 1-4 entirely (SuiteSparse's
      ``sparse2`` spirit: same contract as ``sparse``, faster)
  find(S)                                          (i, j, v) unit-offset
  nnz_of(S)                                        python-int nnz
  mtimes(A, B)                                     Matlab ``A * B`` —
      sparse x dense spmv/spmm, or sparse x sparse via the plan-cached
      two-phase SpGEMM subsystem (:mod:`repro.sparse.spgemm`)
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from ..core.coo import COO, coo_from_matlab, upload_values
from ..core.csc import CSC, slot_columns
from ..core.spans import (EXPAND, FSPARSE, PLAN, PLAN_CACHE, PLAN_KEY,
                          FILL, UPLOAD, span)
from .dispatch import resolve_method
from .lru import LRUCache
from .pattern import (SparsePattern, plan_coo, plan_symmetric,
                      validate_accum)


def expand_indices(ii, jj, ss):
    """fsparse index-expansion (§2.1): broadcast i (col), j (row), s.

    Elementwise mode: equal-length 1-d ``ii``/``jj`` (``ss`` scalar or
    the same length).  Outer-product mode: explicitly 2-d inputs (a
    column ``ii`` and a row ``jj``) or a scalar against a vector; ``ss``
    may be a scalar, the full (ni, nj) grid, a flat vector of ni*nj
    values, or a broadcastable (ni, 1) / (1, nj) slice.  Anything else
    raises the Matlab-compatible errors instead of silently expanding
    or crashing inside ``reshape``.
    """
    with span(EXPAND):
        ii = np.asarray(ii, dtype=np.float64)
        jj = np.asarray(jj, dtype=np.float64)
        grid = _index_grid(ii, jj)
        ss = expand_values(ss, grid)
        if len(grid) == 1:
            return ii.ravel(), jj.ravel(), ss
        # outer-product expansion: i column (ni, 1), j row (1, nj)
        return (np.broadcast_to(ii.reshape(-1, 1), grid).ravel(),
                np.broadcast_to(jj.reshape(1, -1), grid).ravel(), ss)


def _index_grid(ii: np.ndarray, jj: np.ndarray) -> tuple:
    """Shape of the triplet grid ``ii``/``jj`` expand to: ``(L,)``
    elementwise, ``(ni, nj)`` as an outer product."""
    if ii.ndim <= 1 and jj.ndim <= 1:
        if ii.size == jj.size:
            return (ii.size,)
        if ii.size != 1 and jj.size != 1:
            # mismatched 1-d vectors are an error in Matlab, not an
            # implicit outer product (only scalars broadcast)
            raise ValueError("vectors must be the same length")
    return (ii.size, jj.size)


def expand_values(ss, grid: tuple) -> np.ndarray:
    """The flat float64 values of an expansion over ``grid``
    (:func:`_index_grid`), with the Matlab-compatible errors."""
    ss = np.asarray(ss, dtype=np.float64)
    if ss.size == 1:
        return np.full(grid, float(ss.ravel()[0])).ravel()
    if len(grid) == 1:
        if ss.size != grid[0]:
            raise ValueError("vectors must be the same length")
        return ss.ravel()
    ni, nj = grid
    if ss.shape == grid or (ss.ndim == 1 and ss.size == ni * nj):
        return ss.ravel()
    if ss.ndim == 2 and ss.shape in ((ni, 1), (1, nj)):
        return np.broadcast_to(ss, grid).ravel()
    raise ValueError(
        f"cannot expand s of shape {ss.shape} over a ({ni}, {nj}) "
        f"index grid; expected a scalar, ({ni}, {nj}), ({ni}, 1), "
        f"(1, {nj}), or a flat vector of {ni * nj} values"
    )


def fsparse(ii, jj, ss, shape=None, nzmax: int | None = None,
            *, method: str | None = None, mesh=None, accum: str = "sum",
            nzmax_slack: int = 0, format: str | None = None,
            block: int = 1):
    """Assemble a sparse matrix from Matlab-style triplet data.

    >>> import numpy as np
    >>> i, j, s = [3, 2, 3], [1, 2, 1], [7.0, 9.0, 1.0]
    >>> S = fsparse(i, j, s)             # size implied by max indices
    >>> S.shape, int(S.nnz)              # duplicates at (3, 1) summed
    ((3, 2), 2)
    >>> np.asarray(S.to_dense())
    array([[0., 0.],
           [0., 9.],
           [8., 0.]], dtype=float32)

    Other call shapes (explicit size, capacity, backend, distribution)::

        S = fsparse(i, j, s, (m, n))     # explicit size
        S = fsparse(i, j, s, (m, n), nzmax, method="fused")
        S = fsparse(i, j, s, (m, n), method="sharded")   # ShardedCSC
        S = fsparse(i, j, s, (m, n), accum="max")        # accumarray-style

    ``method=None`` resolves to the production planning backend
    (``repro.sparse.dispatch.default_method()`` — ``"radix"`` on TPU,
    ``"fused"`` off-TPU).  ``method="sharded"`` runs the distributed path
    (:mod:`repro.sparse.sharded`) over ``mesh`` (default: one data axis
    over all devices) and returns a block-row :class:`ShardedCSC`; use
    ``convert(S, "csc")`` for the Matlab layout.  ``accum`` selects how
    duplicate (i, j) values combine (``repro.sparse.ACCUM_MODES`` —
    Matlab's ``sparse`` sums; the rest are ``accumarray`` reductions).

    ``format="symcsc"`` assembles through the *halved* symmetric plan
    (:func:`~repro.sparse.pattern.plan_symmetric`): the structure must
    be pairwise symmetric (verified; a clear error names the plain-CSC
    fallback otherwise) and the duplicate-summed values must be too —
    the FEM element-matrix contract; only strict-upper + diagonal
    values are streamed, half the full fill.  ``format="bsr"``
    assembles a plain CSC and groups it into dense ``block x block``
    tiles.  Both compose with ``method=`` planning backends; neither
    supports ``method="sharded"`` (clear error).
    """
    with span(FSPARSE) as request:
        method = method if method == "sharded" else resolve_method(method)
        validate_accum(accum)
        _validate_format(format, block)
        ii, jj, ss = expand_indices(ii, jj, ss)
        request.set_metadata(L=ii.size)
        coo = coo_from_matlab(ii, jj, ss, shape=shape)
        if method == "sharded":
            _reject_sharded_format(format)
            _reject_sharded_accum(accum)
            _reject_sharded_slack(nzmax_slack)
        else:
            _reject_unused_mesh(mesh, method)
        with span(PLAN):
            if method == "sharded":
                pat = _plan_sharded_coo(coo, nzmax, mesh)
            elif format == "symcsc":
                pat = plan_symmetric(np.asarray(coo.rows),
                                     np.asarray(coo.cols), coo.shape,
                                     nzmax=nzmax, method=method, accum=accum)
            else:
                pat = plan_coo(coo, nzmax=nzmax, method=method, accum=accum,
                               nzmax_slack=nzmax_slack)
        with span(FILL):
            out = pat.assemble(coo.vals)
        if format == "bsr":
            from .formats import convert

            return convert(out, "bsr", block=block)
        return out


def _reject_unused_mesh(mesh, method):
    if mesh is not None:
        raise ValueError(
            f"mesh= is only meaningful with method='sharded' "
            f"(got method={method!r}); the mesh would be silently ignored"
        )


def _validate_format(format, block):
    if format not in (None, "symcsc", "bsr"):
        raise ValueError(
            f"unknown assembly format {format!r}; expected None "
            "(plain CSC), 'symcsc' or 'bsr'"
        )
    if int(block) < 1:
        raise ValueError(f"block must be >= 1, got {block}")
    if format != "bsr" and int(block) != 1:
        raise ValueError(
            f"block={block} is only meaningful with format='bsr' "
            f"(got format={format!r}); it would be silently ignored"
        )


def _reject_sharded_format(format):
    if format is not None:
        raise NotImplementedError(
            f"format={format!r} is not supported with method='sharded': "
            "ShardedPattern routes and plans the full triplet stream per "
            "row block and knows nothing about symmetry or block tiles; "
            "fall back to the plain-CSC sharded path (format=None) and "
            "convert() the gathered result instead"
        )


def _reject_sharded_accum(accum):
    if accum != "sum":
        raise ValueError(
            f"accum={accum!r} is not supported with method='sharded' "
            "(the distributed fill reduces with scatter-add); assemble "
            "per-shard with plan(..., accum=...) or drop method='sharded'"
        )


def _reject_sharded_slack(nzmax_slack):
    if nzmax_slack:
        raise ValueError(
            "nzmax_slack is per-pattern growth headroom but sharded "
            "storage is per-block (and ShardedPattern.update is not "
            "supported); pass capacity knobs to plan_sharded directly"
        )


def _plan_sharded_coo(coo: COO, nzmax, mesh):
    from .sharded import plan_sharded

    if nzmax is not None:
        raise ValueError(
            "nzmax is a *global* capacity but sharded storage is "
            "per-block; pass capacity/nzmax to plan_sharded directly"
        )
    pat = plan_sharded(coo.rows, coo.cols, coo.shape, mesh=mesh)
    # overflow is a plan-time property (structure, not values): check it
    # once here — a silent drop would return a wrong matrix.  Cache hits
    # in sparse2 reuse an already-validated plan and skip the sync.
    if bool(pat.any_overflow()):
        raise ValueError(
            "sharded routing bucket overflow: the row distribution is too "
            "skewed for the default capacity; use plan_sharded(...) with a "
            "larger capacity_factor/capacity"
        )
    return pat


def fsparse_coo(coo: COO, nzmax: int | None = None,
                *, method: str | None = None, accum: str = "sum") -> CSC:
    """Zero-offset COO entry point (jit-friendly; no host validation)."""
    return plan_coo(coo, nzmax=nzmax, method=method,
                    accum=accum).assemble(coo.vals)


# ---------------------------------------------------------------------------
# sparse2 — pattern-caching assembly (the serving-cache seed)
# ---------------------------------------------------------------------------
#: the sparse2 symbolic-plan LRU.  Thread-safe (see repro.sparse.lru):
#: concurrent sparse2/PlanService request streams share it.  Capacity
#: is read from REPRO_PLAN_CACHE_SIZE at import; resize at runtime with
#: ``_PLAN_CACHE.resize(n)``.
_PLAN_CACHE = LRUCache(32, name="sparse2-plan", env="REPRO_PLAN_CACHE_SIZE")


def _cache_key(rows: np.ndarray, cols: np.ndarray, shape, nzmax, method,
               extra=()):
    """Structure-identity key for the sparse2 plan cache.

    ``tobytes()`` alone is NOT an identity: two buffers can share bytes
    while describing different structures (an int64 vector aliases two
    int32 indices; a transposed expansion shape ravels identically), so
    the dtypes and *both* shapes are part of the key — a collision here
    would silently return a plan for the wrong structure.
    """
    return (rows.tobytes(), cols.tobytes(),
            rows.shape, cols.shape, rows.dtype.str, cols.dtype.str,
            tuple(shape), nzmax, method, extra)


def plan_lookup(ii, jj, ss, shape=None, nzmax: int | None = None,
                *, method: str | None = None, mesh=None,
                accum: str = "sum", nzmax_slack: int = 0,
                format: str | None = None, block: int = 1):
    """The shared symbolic phase behind ``sparse2`` and the PlanService.

    Validates/expands the Matlab-style request, resolves its cache key
    and returns ``(key, pattern, coo)`` with ``pattern`` served from
    (or inserted into) the thread-safe plan LRU.  ``sparse2`` and
    :class:`repro.sparse.serving.PlanService` reach it through
    :func:`_lookup_values`, which serves a warm request without it —
    one code path, so the entry points cannot drift apart.

    ``nzmax_slack`` folds into the resolved ``nzmax`` (``L + slack``)
    *before* keying, so a slack-planned structure and an explicit
    ``nzmax=L+slack`` request share one cache entry.
    """
    method = method if method == "sharded" else resolve_method(method)
    validate_accum(accum)
    _validate_format(format, block)
    ii, jj, ss = expand_indices(ii, jj, ss)
    coo = coo_from_matlab(ii, jj, ss, shape=shape)
    if nzmax is None and nzmax_slack and method != "sharded":
        nzmax = int(coo.rows.shape[0]) + int(nzmax_slack)
    extra = ()
    if method == "sharded":
        from .sharded import mesh_fingerprint, resolve_mesh

        _reject_sharded_format(format)
        _reject_sharded_accum(accum)
        _reject_sharded_slack(nzmax_slack)
        mesh = resolve_mesh(mesh)
        extra = mesh_fingerprint(mesh, "data")
    else:
        _reject_unused_mesh(mesh, method)
    # accum is part of the plan (a static SparsePattern field), so it is
    # part of the cache identity too; so are the target format and its
    # block size — a SymPattern and a SparsePattern over the same
    # triplets are different resident plans
    rows, cols = coo.rows, coo.cols
    with span(PLAN_KEY, bytes=rows.nbytes + cols.nbytes, hit=0):
        key = _cache_key(np.asarray(rows), np.asarray(cols),
                         coo.shape, nzmax, method,
                         (accum, format, int(block)) + tuple(extra))

    def build():
        with span(PLAN):
            if method == "sharded":
                return _plan_sharded_coo(coo, nzmax, mesh)
            if format == "symcsc":
                return plan_symmetric(np.asarray(rows), np.asarray(cols),
                                      coo.shape, nzmax=nzmax, method=method,
                                      accum=accum)
            return plan_coo(coo, nzmax=nzmax, method=method, accum=accum)

    with span(PLAN_CACHE):
        pat = _PLAN_CACHE.get_or_create(key, build)
    return key, pat, coo


# ---------------------------------------------------------------------------
# The warm path: raw index vectors as aliases of a plan-LRU key
# ---------------------------------------------------------------------------
#: raw request -> canonical plan-LRU key, for index vectors that a
#: successful lookup validated.  Keyed on what the caller passed (index
#: dtypes and shapes, a strided sample of their elements, and every
#: argument that enters the plan key); a hit still needs every index
#: byte to match the copy stored here.  Sized like the plan LRU.
_ALIASES = LRUCache(32, name="sparse2-alias", env="REPRO_PLAN_CACHE_SIZE")

#: elements of each index vector sampled into the alias key
_ALIAS_SAMPLE = 16


class _Alias(NamedTuple):
    ii: np.ndarray   # read-only copies of the validated index vectors
    jj: np.ndarray
    grid: tuple      # what they expand to (``_index_grid``)
    key: tuple       # the plan-LRU key they resolved to


def _alias_key(ii: np.ndarray, jj: np.ndarray, shape, args: tuple):
    """The alias-store key of a request, or None where the request can
    only take the cold path (an index dtype without a plain byte
    compare, a ``shape`` the cold path would reject)."""
    for v in (ii, jj):
        if v.dtype.kind not in "iuf" or v.itemsize not in (1, 2, 4, 8):
            return None
    if shape is not None:
        try:
            shape = (int(shape[0]), int(shape[1]))
        except (TypeError, ValueError, IndexError):
            return None
    sample = tuple(v.flat[np.linspace(0, v.size - 1, _ALIAS_SAMPLE,
                                      dtype=np.intp)].tobytes()
                   if v.size else b"" for v in (ii, jj))
    return (ii.dtype.str, ii.shape, jj.dtype.str, jj.shape, shape,
            *sample, *args)


def _same(a: np.ndarray, b: np.ndarray, chunk: int = 1 << 18) -> bool:
    """Every byte of ``a`` (the caller's) equals ``b`` (a C-contiguous
    copy of one dtype and shape); compared a cache-sized chunk at a
    time, as unsigned integers so that floats compare by bits."""
    u = np.dtype(f"u{a.itemsize}")
    a, b = a.reshape(-1).view(u), b.reshape(-1).view(u)
    return all(np.array_equal(a[s:s + chunk], b[s:s + chunk])
               for s in range(0, a.size, chunk))


def _frozen(v: np.ndarray) -> np.ndarray:
    out = np.array(v, order="C")
    out.flags.writeable = False
    return out


def _lookup_values(ii, jj, ss, shape=None, nzmax: int | None = None,
                   *, method: str | None = None, mesh=None,
                   accum: str = "sum", nzmax_slack: int = 0,
                   format: str | None = None, block: int = 1):
    """:func:`plan_lookup` for callers that need only the values:
    returns ``(key, pattern, vals)``, ``vals`` the float32 device values.

    Index vectors byte-identical (same dtype and shape) to ones an
    earlier successful lookup validated, with the same arguments, are a
    warm request: it skips expansion, validation, the index upload and
    the key, checks and uploads only the values, and returns the very
    key object that lookup stored.  Everything else takes
    :func:`plan_lookup`'s path unchanged and, when that returns, is
    recorded for the next call.
    """
    method = method if method == "sharded" else resolve_method(method)
    validate_accum(accum)
    _validate_format(format, block)
    ii, jj = np.asarray(ii), np.asarray(jj)
    mesh_id = ()
    if method == "sharded":
        from .sharded import mesh_fingerprint, resolve_mesh

        mesh = resolve_mesh(mesh)
        mesh_id = mesh_fingerprint(mesh, "data")
    akey = None
    if method == "sharded" or mesh is None:  # else the cold path raises
        akey = _alias_key(ii, jj, shape, (nzmax, method, accum, nzmax_slack,
                                          format, int(block), mesh_id))
    if akey is not None:
        hit = _ALIASES.get_verified(akey, lambda a: _warm(ii, jj, a))
        if hit is not None:
            pat = _PLAN_CACHE.get(hit.key)
            if pat is not None:
                with span(UPLOAD, bytes=4 * math.prod(hit.grid)):
                    vals = upload_values(expand_values(ss, hit.grid))
                return hit.key, pat, vals
    key, pat, coo = plan_lookup(ii, jj, ss, shape, nzmax, method=method,
                                mesh=mesh, accum=accum,
                                nzmax_slack=nzmax_slack, format=format,
                                block=block)
    if akey is not None:
        _ALIASES.pop(akey)
        _ALIASES.insert(akey, _Alias(_frozen(ii), _frozen(jj),
                                     _index_grid(ii, jj), key))
    return key, pat, coo.vals


def _warm(ii: np.ndarray, jj: np.ndarray, alias: _Alias) -> bool:
    """Whether ``alias`` answers the request: every index byte equal and
    its plan still in the plan LRU."""
    with span(PLAN_KEY, bytes=ii.nbytes + jj.nbytes) as s:
        ok = (_same(ii, alias.ii) and _same(jj, alias.jj)
              and alias.key in _PLAN_CACHE)
        s.set_metadata(hit=int(ok))
    return ok


def sparse2(ii, jj, ss, shape=None, nzmax: int | None = None,
            *, method: str | None = None, mesh=None, accum: str = "sum",
            nzmax_slack: int = 0, format: str | None = None,
            block: int = 1):
    """``fsparse`` with symbolic-plan reuse across calls.

    Same contract and results as :func:`fsparse`; repeated calls whose
    index vectors (and shape/nzmax/method/accum) are identical hit a
    thread-safe host-side LRU of :class:`SparsePattern` plans and run
    only the O(L) numeric phase.  This is the repeated-assembly FEM
    workflow (fixed mesh, changing element values) as a drop-in call.

    ``method="sharded"`` caches :class:`~repro.sparse.sharded.ShardedPattern`
    plans the same way (keyed additionally on the mesh), so repeated
    distributed assembly pays routing + per-block analysis once.

    ``format="symcsc"`` caches the *halved*
    :class:`~repro.sparse.pattern.SymPattern` (strict-upper + diagonal
    slots only) so every refill streams half the values;
    ``format="bsr"`` caches the plain plan and groups each assembled
    result into dense ``block x block`` tiles.  The format (and block)
    are part of the cache key.
    """
    _, pat, vals = _lookup_values(ii, jj, ss, shape, nzmax, method=method,
                                  mesh=mesh, accum=accum,
                                  nzmax_slack=nzmax_slack, format=format,
                                  block=block)
    out = pat.assemble(vals)
    if format == "bsr":
        from .formats import convert

        return convert(out, "bsr", block=block)
    return out


# ---------------------------------------------------------------------------
# Delta re-planning facade (SparsePattern.update through the plan cache)
# ---------------------------------------------------------------------------
class PlanUpdate(NamedTuple):
    """Result of :func:`plan_update`.

    ``key``/``pattern`` identify the *updated* structure in the plan
    LRU; ``coo`` is the concatenated (surviving + delta) zero-offset
    triplet stream whose values align with ``pattern`` (so
    ``pattern.assemble(coo.vals)`` is the updated matrix).  ``old_key``/
    ``old_pattern`` are the pre-update entry — equal to the new ones
    when the update was a no-op — so callers (the serving layer) can
    retire executables and persisted entries keyed on the old structure.
    """

    key: tuple
    pattern: SparsePattern
    coo: COO
    old_key: tuple
    old_pattern: SparsePattern


def plan_update(ii, jj, ss, add_ii, add_jj, add_ss, shape=None,
                nzmax: int | None = None, *, drop_mask=None,
                method: str | None = None, accum: str = "sum",
                nzmax_slack: int = 0) -> PlanUpdate:
    """Delta re-planning through the ``sparse2`` plan cache.

    ``(ii, jj, ss, shape, nzmax[, nzmax_slack], method, accum)``
    identify the *base* structure exactly as a ``sparse2`` call would
    (a cold base is planned and cached first); ``add_ii``/``add_jj``/
    ``add_ss`` are unit-offset Matlab-style delta triplets (validated
    against the base shape — growing the shape is a re-plan, not an
    update) and ``drop_mask`` flags expanded base triplets to remove.
    The base plan is rewritten by :meth:`SparsePattern.update` (epoch
    bumped, merge-by-key — see there for the capacity/fallback
    contract), the LRU entry moves from the old key to the
    concatenated-stream key in place, and dependent SpGEMM products
    are retired lazily via
    :func:`repro.sparse.spgemm.retire_structure`.

    The new entry is keyed with the updated pattern's concrete
    ``nzmax``, so a later ``sparse2(cat_i, cat_j, cat_s, shape,
    nzmax=result.pattern.nzmax)`` over the concatenated triplets hits
    it without re-planning.
    """
    method = resolve_method(method)
    if method == "sharded":
        raise ValueError(
            "plan_update does not support method='sharded': deltas are "
            "not routed per row block (ShardedPattern.update raises); "
            "re-plan with plan_sharded"
        )
    validate_accum(accum)
    bi, bj, bs = expand_indices(ii, jj, ss)
    coo = coo_from_matlab(bi, bj, bs, shape=shape)
    L = int(coo.rows.shape[0])
    if nzmax is None and nzmax_slack:
        nzmax = L + int(nzmax_slack)
    rows_b = np.asarray(coo.rows)
    cols_b = np.asarray(coo.cols)
    # extras mirror plan_lookup's plain-CSC identity (format=None,
    # block=1): delta updates only refine plain plans, and the keys
    # must collide with the ones sparse2/assemble recorded
    old_key = _cache_key(rows_b, cols_b, coo.shape, nzmax, method,
                         (accum, None, 1))
    base = _PLAN_CACHE.get_or_create(
        old_key,
        lambda: plan_coo(coo, nzmax=nzmax, method=method, accum=accum),
    )
    # delta validated against the *base* shape: an out-of-range delta
    # index raises Matlab's "index exceeds matrix dimensions" here
    di, dj, dv = expand_indices(add_ii, add_jj, add_ss)
    dcoo = coo_from_matlab(di, dj, dv, shape=coo.shape)
    new_pat = base.update(np.asarray(dcoo.rows), np.asarray(dcoo.cols),
                          drop_mask=drop_mask, method=method)
    vals_b = np.asarray(coo.vals)
    if drop_mask is not None:
        dm = np.asarray(drop_mask).astype(bool)
        if dm.any():
            keep = ~dm
            rows_b, cols_b = rows_b[keep], cols_b[keep]
            vals_b = vals_b[keep]
    rows_cat = np.concatenate([rows_b, np.asarray(dcoo.rows)])
    cols_cat = np.concatenate([cols_b, np.asarray(dcoo.cols)])
    vals_cat = np.concatenate([vals_b, np.asarray(dcoo.vals)])
    new_coo = COO(rows=jnp.asarray(rows_cat), cols=jnp.asarray(cols_cat),
                  vals=jnp.asarray(vals_cat), shape=coo.shape)
    if new_pat is base:  # no-op update: nothing moved, nothing retired
        return PlanUpdate(old_key, base, new_coo, old_key, base)
    new_key = _cache_key(rows_cat, cols_cat, coo.shape, new_pat.nzmax,
                         method, (accum, None, 1))
    _PLAN_CACHE.pop(old_key)
    new_pat = _PLAN_CACHE.insert(new_key, new_pat)
    from .spgemm import _structure_key, retire_structure

    retire_structure(_structure_key(base))
    return PlanUpdate(new_key, new_pat, new_coo, old_key, base)


def sparse2_update(ii, jj, ss, add_ii, add_jj, add_ss, shape=None,
                   nzmax: int | None = None, *, drop_mask=None,
                   method: str | None = None, accum: str = "sum",
                   nzmax_slack: int = 0) -> CSC:
    """Incrementally re-planned ``sparse2``: refine, then refill.

    Returns the assembled matrix of the concatenated (surviving base +
    delta) triplets — bit-identical to ``fsparse`` over that stream
    with the same capacity — while the cached symbolic plan is *merged
    forward* (:func:`plan_update`) instead of thrown away: only the
    delta is sorted, and subsequent ``sparse2``/``plan_update`` calls
    against the updated structure keep hitting the cache.
    """
    res = plan_update(ii, jj, ss, add_ii, add_jj, add_ss, shape, nzmax,
                      drop_mask=drop_mask, method=method, accum=accum,
                      nzmax_slack=nzmax_slack)
    return res.pattern.assemble(res.coo.vals)


def plan_cache_info() -> dict:
    """Introspection for tests/ops: sparse2 plan-cache state.

    The historical ``size``/``capacity`` keys are kept; ``hits``/
    ``misses``/``evictions``/``insertions`` are the serving metrics of
    the shared locked LRU.
    """
    return _PLAN_CACHE.info()


def alias_cache_info() -> dict:
    """The warm path's alias store (:func:`_lookup_values`): its
    ``hits`` are requests served without touching their indices."""
    return _ALIASES.info()


def plan_cache_clear() -> None:
    _PLAN_CACHE.clear()
    _ALIASES.clear()


# ---------------------------------------------------------------------------
# Matlab query helpers
# ---------------------------------------------------------------------------
def find(S):
    """Matlab ``[i, j, v] = find(S)``: unit-offset triplets of nonzeros.

    Host-side (numpy) — the columnwise, row-ascending order matches
    Matlab's.  Structural zeros (cancelled duplicates) are reported,
    exactly like fsparse/sparse keep them.  Non-CSC formats (SymCSC,
    BSR, CSR, COO, ...) convert through the format registry first, so
    ``find`` reports the *expanded* structure (a SymCSC's mirrored
    lower triangle and dense diagonal included).
    """
    if not isinstance(S, CSC):
        from .formats import convert

        S = convert(S, "csc")
    nnz = int(S.nnz)
    cols = np.asarray(slot_columns(S.indptr, S.nzmax))[:nnz]
    rows = np.asarray(S.indices)[:nnz]
    vals = np.asarray(S.data)[:nnz]
    return rows + 1, cols + 1, vals


def mtimes(A, B):
    """Matlab ``A * B`` on sparse operands.

    A dense ``B`` runs spmv/spmm; a sparse ``B`` (any registered
    format) runs the two-phase SpGEMM path — the symbolic product plan
    is cached across calls keyed on both structures (like the
    ``sparse2`` plan cache), so Matlab-style repeated products such as
    the multigrid Galerkin triple product ``P' * A * P`` pay only the
    O(flops) numeric refill after the first call.

    >>> import numpy as np
    >>> A = fsparse([1, 2], [1, 2], [2.0, 3.0])      # diag(2, 3)
    >>> np.asarray(mtimes(A, A).to_dense())
    array([[4., 0.],
           [0., 9.]], dtype=float32)
    """
    from .ops import matmul

    return matmul(A, B)


def nnz_of(S) -> int:
    """Matlab ``nnz(S)`` — structural nonzero count as a python int.

    Accepts any registered format whose ``nnz`` is a scalar or (for
    block-partitioned formats like ``ShardedCSC``) a per-block vector;
    blocks partition the matrix, so the counts sum.  Formats that store
    a compressed half/blocked structure (SymCSC, BSR) expose the
    Matlab-visible expanded count as ``nnz_total`` — preferred here.
    """
    total = getattr(S, "nnz_total", None)
    if total is not None:
        return int(np.asarray(total))
    return int(np.sum(np.asarray(S.nnz)))
