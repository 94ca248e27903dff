"""Two-phase API: SparsePattern reuse, formats/protocol, Matlab facade."""
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.sparse import (
    COO,
    CSC,
    CSR,
    SparseMatrix,
    SparsePattern,
    available_methods,
    convert,
    find,
    format_of,
    fsparse,
    nnz_of,
    plan,
    plan_cache_clear,
    plan_cache_info,
    sparse2,
)
from repro.core import assemble_arrays, assemble_fused
from repro.core import fsparse as core_fsparse
from repro.core.assemble import assemble
from repro.core.coo import coo_from_matlab
from repro.core.oracle import matlab_sparse_oracle

scipy_sparse = pytest.importorskip("scipy.sparse")


def _triplets(seed, L, M, N):
    rng = np.random.default_rng(seed)
    return (
        rng.integers(0, M, L).astype(np.int32),
        rng.integers(0, N, L).astype(np.int32),
        rng.normal(size=L).astype(np.float32),
    )


def _scipy_csc(rows, cols, vals, M, N):
    return scipy_sparse.coo_matrix(
        (vals.astype(np.float64), (rows, cols)), shape=(M, N)
    ).tocsc()


def _assert_matches_scipy(S: CSC, rows, cols, vals, M, N):
    ref = _scipy_csc(rows, cols, vals, M, N)
    nnz = int(S.nnz)
    # scipy drops nothing here (no explicit zero elimination was called)
    assert nnz == ref.nnz
    np.testing.assert_array_equal(np.asarray(S.indptr), ref.indptr)
    np.testing.assert_array_equal(np.asarray(S.indices)[:nnz], ref.indices)
    np.testing.assert_allclose(
        np.asarray(S.data)[:nnz], ref.data, rtol=2e-5, atol=1e-5
    )


# ---------------------------------------------------------------------------
# Pattern-reuse equivalence vs fsparse and the scipy oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("method", ["jnp", "fused", "pallas"])
@pytest.mark.parametrize("L,M,N", [(1, 1, 1), (200, 7, 13), (5000, 100, 80)])
def test_plan_assemble_equals_fsparse_and_scipy(method, L, M, N):
    rows, cols, vals = _triplets(L * 3 + M, L, M, N)
    pat = plan(rows, cols, (M, N), method=method)
    S = pat.assemble(jnp.asarray(vals))
    F = fsparse(rows + 1, cols + 1, vals, (M, N), method=method)
    _assert_matches_scipy(S, rows, cols, vals, M, N)
    np.testing.assert_array_equal(np.asarray(S.indices), np.asarray(F.indices))
    np.testing.assert_array_equal(np.asarray(S.indptr), np.asarray(F.indptr))
    np.testing.assert_allclose(
        np.asarray(S.data), np.asarray(F.data), rtol=2e-5, atol=1e-5
    )


def test_pattern_reuse_many_value_vectors():
    """One symbolic plan, many numeric fills — all match the oracle."""
    rows, cols, _ = _triplets(0, 3000, 50, 60)
    pat = plan(rows, cols, (50, 60))
    rng = np.random.default_rng(1)
    for _ in range(3):
        vals = rng.normal(size=3000).astype(np.float32)
        S = pat.assemble(jnp.asarray(vals))
        _assert_matches_scipy(S, rows, cols, vals, 50, 60)


def test_duplicate_pairs_sum():
    rows = np.array([0, 0, 0, 2, 2], np.int32)
    cols = np.array([1, 1, 1, 0, 0], np.int32)
    vals = np.array([1.0, 2.0, 3.0, 10.0, -10.0], np.float32)
    pat = plan(rows, cols, (3, 3))
    S = pat.assemble(jnp.asarray(vals))
    dense = np.asarray(S.to_dense())
    assert dense[0, 1] == pytest.approx(6.0)
    assert dense[2, 0] == pytest.approx(0.0)   # cancelled but structural
    assert int(S.nnz) == 2                      # fsparse keeps the slot
    _assert_matches_scipy(S, rows, cols, vals, 3, 3)


def test_padding_sentinels_dropped():
    """row == M inputs (all_to_all padding) vanish from the plan."""
    rows = np.array([0, 3, 3, 1, 3], np.int32)  # M == 3 -> three pads
    cols = np.array([0, 1, 2, 1, 0], np.int32)
    vals = np.array([1.0, 9.0, 9.0, 2.0, 9.0], np.float32)
    pat = plan(rows, cols, (3, 3))
    S = pat.assemble(jnp.asarray(vals))
    assert int(S.nnz) == 2
    assert np.asarray(S.to_dense()).sum() == pytest.approx(3.0)
    # padded tail is inert
    assert np.all(np.asarray(S.indices)[2:] == 3)
    assert np.all(np.asarray(S.data)[2:] == 0)


def test_assemble_batch_shares_structure():
    rows, cols, _ = _triplets(7, 1000, 30, 40)
    pat = plan(rows, cols, (30, 40))
    vb = np.random.default_rng(2).normal(size=(5, 1000)).astype(np.float32)
    Sb = pat.assemble_batch(jnp.asarray(vb))
    assert Sb.data.shape == (5, 1000)
    nnz = int(Sb.nnz)
    for b in range(5):
        pr, ir, jc = matlab_sparse_oracle(rows, cols, vb[b], 30, 40)
        assert nnz == len(pr)
        np.testing.assert_allclose(
            np.asarray(Sb.data[b])[:nnz], pr, rtol=2e-5, atol=1e-5
        )


def test_pattern_is_jit_and_vmap_compatible():
    rows, cols, vals = _triplets(11, 500, 20, 20)
    pat = plan(rows, cols, (20, 20))

    @jax.jit
    def fill(p: SparsePattern, v):
        return p.assemble(v).data

    d1 = fill(pat, jnp.asarray(vals))
    np.testing.assert_allclose(
        np.asarray(d1), np.asarray(pat.assemble(jnp.asarray(vals)).data)
    )
    vb = jnp.asarray(np.stack([vals, 2 * vals]))
    dv = jax.vmap(lambda v: pat.scatter(v))(vb)
    np.testing.assert_allclose(np.asarray(dv[1]), 2 * np.asarray(dv[0]),
                               rtol=1e-5, atol=1e-5)


def test_irank_matches_paper_running_example():
    i_in = np.array([3, 4, 1, 3, 2, 1, 4, 4, 4, 3, 2, 3, 1]) - 1
    j_in = np.array([3, 3, 1, 4, 1, 1, 4, 3, 1, 3, 2, 2, 4]) - 1
    pat = plan(i_in, j_in, (4, 4))
    assert np.asarray(pat.irank()).tolist() == \
        [5, 6, 0, 8, 1, 0, 9, 6, 2, 5, 3, 4, 7]
    assert np.asarray(pat.indptr).tolist() == [0, 3, 5, 7, 10]
    assert int(pat.nnz) == 10


# ---------------------------------------------------------------------------
# Formats: protocol, registry, CSR round-trip
# ---------------------------------------------------------------------------
def test_protocol_and_registry():
    rows, cols, vals = _triplets(5, 400, 25, 35)
    S = plan(rows, cols, (25, 35)).assemble(jnp.asarray(vals))
    assert isinstance(S, SparseMatrix)
    assert format_of(S) == "csc"
    R = convert(S, "csr")
    assert isinstance(R, CSR) and isinstance(R, SparseMatrix)
    assert format_of(R) == "csr"
    C = convert(S, "coo")
    assert isinstance(C, COO) and isinstance(C, SparseMatrix)
    assert convert(S, "csc") is S  # identity short-circuit
    with pytest.raises(ValueError):
        convert(S, "ell")


def test_csr_round_trip():
    """csc -> csr -> csc preserves values, structure, and nnz."""
    rows, cols, vals = _triplets(13, 2000, 60, 45)
    S = plan(rows, cols, (60, 45)).assemble(jnp.asarray(vals))
    R = convert(S, "csr")
    ref = _scipy_csc(rows, cols, vals, 60, 45).tocsr()
    nnz = int(R.nnz)
    assert nnz == ref.nnz
    np.testing.assert_array_equal(np.asarray(R.indptr), ref.indptr)
    np.testing.assert_array_equal(np.asarray(R.indices)[:nnz], ref.indices)
    np.testing.assert_allclose(np.asarray(R.data)[:nnz], ref.data,
                               rtol=2e-5, atol=1e-5)
    S2 = convert(R, "csc")
    assert int(S2.nnz) == int(S.nnz)
    np.testing.assert_allclose(
        np.asarray(S2.to_dense()), np.asarray(S.to_dense()),
        rtol=1e-5, atol=1e-5,
    )


def test_methods_registry_reports_builtins():
    assert {"jnp", "fused", "pallas"} <= set(available_methods())


# ---------------------------------------------------------------------------
# Matlab facade
# ---------------------------------------------------------------------------
def test_find_matches_matlab_order():
    S = fsparse([3, 1, 2, 3], [1, 1, 2, 1], [1.0, 2.0, 3.0, 4.0], (3, 2))
    fi, fj, fv = find(S)
    # columnwise, rows ascending within each column
    assert fi.tolist() == [1, 3, 2]
    assert fj.tolist() == [1, 1, 2]
    np.testing.assert_allclose(fv, [2.0, 5.0, 3.0])
    assert nnz_of(S) == 3


def test_sparse2_caches_and_reassembles():
    plan_cache_clear()
    rows, cols, _ = _triplets(3, 600, 40, 40)
    rng = np.random.default_rng(4)
    v1 = rng.normal(size=600)
    v2 = rng.normal(size=600)
    S1 = sparse2(rows + 1, cols + 1, v1, (40, 40))
    assert plan_cache_info()["size"] == 1
    S2 = sparse2(rows + 1, cols + 1, v2, (40, 40))
    assert plan_cache_info()["size"] == 1   # plan was reused
    _assert_matches_scipy(S2, rows, cols, v2.astype(np.float32), 40, 40)
    # different structure -> new plan
    sparse2(cols + 1, rows + 1, v1, (40, 40))
    assert plan_cache_info()["size"] == 2
    np.testing.assert_array_equal(np.asarray(S1.indices),
                                  np.asarray(S2.indices))


def test_convert_to_sharded_roundtrip_single_device():
    """convert(A, 'sharded') goes through the COO hub, not infinite
    recursion (regression: no from-hub converter used to exist)."""
    from repro.launch.mesh import make_data_mesh
    from repro.sparse import ShardedCSC

    rows, cols, vals = _triplets(29, 400, 20, 24)
    S = plan(rows, cols, (20, 24)).assemble(jnp.asarray(vals))
    # pin a 1-device mesh: the default spans ALL devices, whatever
    # host-device count the process was started with
    Sh = convert(S, "sharded", mesh=make_data_mesh(1))
    assert isinstance(Sh, ShardedCSC) and format_of(Sh) == "sharded"
    np.testing.assert_allclose(np.asarray(Sh.to_dense()),
                               np.asarray(S.to_dense()),
                               rtol=1e-5, atol=1e-5)
    back = convert(Sh, "csc")
    assert int(back.nnz) == int(S.nnz)


def test_elementwise_column_vector_values():
    """Matlab's canonical s-as-column-vector call keeps working."""
    S = fsparse([1, 2, 3], [1, 2, 3],
                np.array([[1.0], [2.0], [3.0]]), (3, 3))
    np.testing.assert_allclose(np.asarray(S.to_dense()),
                               np.diag([1.0, 2.0, 3.0]))


def test_mesh_without_sharded_method_raises():
    """mesh= must not be silently ignored on single-device methods."""
    with pytest.raises(ValueError, match="sharded"):
        fsparse([1], [1], [1.0], (2, 2), mesh=object())
    with pytest.raises(ValueError, match="sharded"):
        sparse2([1], [1], [1.0], (2, 2), mesh=object())


def test_sparse2_cache_key_distinguishes_dtype_and_shape():
    """Regression: the plan-cache key must be a structure *identity*.

    ``tobytes()`` alone collides for buffers that alias byte-wise while
    describing different structures — an int64 vector shares bytes with
    two int32 indices, and a float32 view shares bytes with an int32
    array.  A collision silently returns a plan for the wrong structure.
    """
    from repro.sparse.matlab import _cache_key

    rows = np.array([1, 2], np.int32)
    cols32 = np.array([1, 0], np.int32)
    cols64 = np.array([1], np.int64)
    assert cols32.tobytes() == cols64.tobytes()
    # cols dtype/shape byte-aliasing must split the key (the old key
    # carried neither cols.shape nor any dtype)
    assert _cache_key(rows, cols32, (3, 3), None, "jnp") != \
        _cache_key(rows[:1], cols64, (3, 3), None, "jnp")
    # dtype-only difference (same bytes, same shape) must split it too
    f32 = rows.view(np.float32)
    assert rows.tobytes() == f32.tobytes() and rows.shape == f32.shape
    assert _cache_key(rows, cols32, (3, 3), None, "jnp") != \
        _cache_key(f32, cols32, (3, 3), None, "jnp")


def test_expand_indices_mismatched_vectors_raise():
    """Matlab-compatible error instead of a silent outer product."""
    with pytest.raises(ValueError, match="same length"):
        fsparse([1, 2, 3], [1, 2], 1.0, (3, 3))
    with pytest.raises(ValueError, match="same length"):
        fsparse([1, 2], [1, 2], [1.0, 2.0, 3.0], (3, 3))


def test_expand_indices_outer_product_value_shapes():
    ii = np.array([[1], [2]])          # explicit column
    jj = np.array([1, 2, 3])           # row
    # scalar fill
    S = fsparse(ii, jj, 7.0, (2, 3))
    np.testing.assert_allclose(np.asarray(S.to_dense()), 7 * np.ones((2, 3)))
    # flat vector of ni*nj values lays out row-major over the grid
    S = fsparse(ii, jj, np.arange(1.0, 7.0), (2, 3))
    np.testing.assert_allclose(
        np.asarray(S.to_dense()), np.arange(1.0, 7.0).reshape(2, 3)
    )
    # (ni, 1) and (1, nj) slices broadcast
    S = fsparse(ii, jj, np.array([[2.0], [3.0]]), (2, 3))
    np.testing.assert_allclose(
        np.asarray(S.to_dense()), np.array([[2.0] * 3, [3.0] * 3])
    )
    # 1-d scalar-vs-vector stays an outer product (scalars broadcast)
    S = fsparse([2], [1, 2, 3], 5.0, (2, 3))
    np.testing.assert_allclose(
        np.asarray(S.to_dense()), np.array([[0.0] * 3, [5.0] * 3])
    )
    # wrong-sized s raises the clean shape error, not a reshape crash
    with pytest.raises(ValueError, match="cannot expand s"):
        fsparse(ii, jj, np.arange(1.0, 5.0), (2, 3))


# ---------------------------------------------------------------------------
# Deprecation shims
# ---------------------------------------------------------------------------
def test_fused_flag_deprecated_but_working():
    rows, cols, vals = _triplets(17, 300, 15, 15)
    with pytest.warns(DeprecationWarning):
        S = core_fsparse(rows + 1, cols + 1, vals, (15, 15), fused=True)
    _assert_matches_scipy(S, rows, cols, vals, 15, 15)
    coo = coo_from_matlab(rows + 1, cols + 1, vals, (15, 15))
    with pytest.warns(DeprecationWarning):
        S2 = assemble(coo, fused=False)
    _assert_matches_scipy(S2, rows, cols, vals, 15, 15)


def test_old_entry_points_silent_without_fused_flag():
    rows, cols, vals = _triplets(19, 300, 15, 15)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        S = core_fsparse(rows + 1, cols + 1, vals, (15, 15))
        Sa = assemble_arrays(rows, cols, vals, M=15, N=15)
        Sf = assemble_fused(rows, cols, vals, M=15, N=15)
    for X in (S, Sa, Sf):
        _assert_matches_scipy(X, rows, cols, vals, 15, 15)


def test_assemble_method_dispatch():
    rows, cols, vals = _triplets(23, 300, 15, 15)
    coo = coo_from_matlab(rows + 1, cols + 1, vals, (15, 15))
    for method in ("jnp", "fused", "pallas"):
        S = assemble(coo, method=method)
        _assert_matches_scipy(S, rows, cols, vals, 15, 15)
    with pytest.raises(ValueError):
        plan(rows, cols, (15, 15), method="nope")
