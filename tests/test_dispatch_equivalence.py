"""Backend equivalence: every registered sort method, one contract.

All planning backends (``jnp`` / ``fused`` / ``pallas`` / ``radix``)
must produce *identical* ``SparsePattern``s — same stable (col,row)
permutation, same slots/indices/indptr/nnz — on every stream shape the
assembly contract admits: duplicate-heavy, padding sentinels
(``row == M``), empty, and fused keys near/over the int32 boundary.
The suite is what lets ``pattern_from_perm`` and the numeric phase stay
backend-agnostic.
"""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis_compat import given, settings, st

from repro.core.oracle import matlab_sparse_oracle
from repro.core.ransparse import dataset
from repro.sparse import available_methods, default_method, plan
from repro.sparse import dispatch

# every registered single-device backend; "sharded" is a facade path,
# not a sort backend, so it never appears here
METHODS = available_methods()


def _case(name):
    import zlib

    rng = np.random.default_rng(zlib.crc32(name.encode()))  # deterministic
    if name == "dup_heavy":
        # 64 distinct pairs, each repeated 32x (shuffled): the reduce
        # and dedup paths dominate
        base_r = rng.integers(0, 13, 64)
        base_c = rng.integers(0, 11, 64)
        p = rng.permutation(64 * 32)
        return (np.tile(base_r, 32)[p].astype(np.int32),
                np.tile(base_c, 32)[p].astype(np.int32), 13, 11)
    if name == "padding_sentinels":
        # a third of the stream is all_to_all padding (row == M)
        rows = rng.integers(0, 10, 300)
        rows[rng.random(300) < 0.33] = 9
        M = 9  # row 9 == M is the sentinel
        return (rows.astype(np.int32),
                rng.integers(0, 7, 300).astype(np.int32), M, 7)
    if name == "empty":
        return (np.zeros(0, np.int32), np.zeros(0, np.int32), 5, 4)
    if name == "near_int32_key":
        # (M+1)*(N+1) = 46340^2 < 2^31: the fused int32 key *just* fits
        M = N = 46339
        return (rng.integers(0, M, 400).astype(np.int32),
                rng.integers(0, N, 400).astype(np.int32), M, N)
    if name == "over_int32_key":
        # (M+1)*(N+1) = 46342^2 >= 2^31: no int32 fused key exists;
        # "radix" must not have any fallback path here
        M = N = 46341
        return (rng.integers(0, M, 400).astype(np.int32),
                rng.integers(0, N, 400).astype(np.int32), M, N)
    raise AssertionError(name)


CASES = ["dup_heavy", "padding_sentinels", "empty", "near_int32_key",
         "over_int32_key"]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("method", [m for m in METHODS if m != "jnp"])
def test_all_methods_produce_identical_patterns(case, method):
    rows, cols, M, N = _case(case)
    ref = plan(rows, cols, (M, N), method="jnp")
    pat = plan(rows, cols, (M, N), method=method)
    for field in ("perm", "slot", "indices", "indptr"):
        np.testing.assert_array_equal(
            np.asarray(getattr(pat, field)),
            np.asarray(getattr(ref, field)),
            err_msg=f"{method}/{case}/{field}",
        )
    assert int(pat.nnz) == int(ref.nnz)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), L=st.integers(1, 400),
       M=st.integers(1, 50), N=st.integers(1, 50))
def test_all_methods_agree_property(seed, L, M, N):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, M + 1, L).astype(np.int32)  # sentinel included
    cols = rng.integers(0, N, L).astype(np.int32)
    perms = {
        m: np.asarray(plan(rows, cols, (M, N), method=m).perm)
        for m in METHODS
    }
    ref = perms.pop("jnp")
    for m, p in perms.items():
        np.testing.assert_array_equal(p, ref, err_msg=m)


def test_default_method_is_backend_aware():
    import jax

    want = "radix" if jax.default_backend() == "tpu" else "fused"
    assert default_method() == want
    assert dispatch.resolve_method(None) == want
    assert dispatch.DEFAULT_METHOD_TPU == "radix"  # production backend
    assert dispatch.resolve_method("radix") == "radix"
    # method=None (the default) must match the explicit radix plan —
    # equivalence makes the backend-aware default invisible to results
    rows, cols, M, N = _case("dup_heavy")
    pat = plan(rows, cols, (M, N))
    ref = plan(rows, cols, (M, N), method="radix")
    np.testing.assert_array_equal(np.asarray(pat.perm), np.asarray(ref.perm))


@pytest.mark.parametrize("k", [1, 2, 3])
def test_radix_bit_identical_to_matlab_oracle_table42(k):
    """method="radix" plans on the (scaled) Table 4.2 sets reproduce the
    NumPy Matlab oracle bit-for-bit — the acceptance criterion."""
    ii, jj, ss, siz = dataset(k, seed=42, scale=0.01)
    rows = (ii - 1).astype(np.int32)
    cols = (jj - 1).astype(np.int32)
    pat = plan(rows, cols, (siz, siz), method="radix")
    S = pat.assemble(jnp.asarray(ss.astype(np.float32)))
    pr, ir, jc = matlab_sparse_oracle(rows, cols, ss, siz, siz)
    nnz = int(S.nnz)
    assert nnz == len(pr)
    np.testing.assert_array_equal(np.asarray(S.indices)[:nnz], ir)
    np.testing.assert_array_equal(np.asarray(S.indptr), jc)
    np.testing.assert_allclose(np.asarray(S.data)[:nnz], pr,
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# fused-key overflow handling (satellite: no *silent* degradation)
# ---------------------------------------------------------------------------
def test_fused_overflow_warns_once_without_x64():
    rows = np.array([0, 5, 3], np.int32)
    cols = np.array([1, 0, 2], np.int32)
    M = N = 46341  # (M+1)^2 >= 2^31
    dispatch._reset_fused_fallback_warning()
    with pytest.warns(RuntimeWarning, match="overflows int32"):
        p = dispatch.sorted_permutation(rows, cols, M=M, N=N,
                                        method="fused")
    # one-time: a second overflowing call stays silent
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", RuntimeWarning)
        p2 = dispatch.sorted_permutation(rows, cols, M=M, N=N,
                                         method="fused")
    ref = dispatch.sorted_permutation(rows, cols, M=M, N=N, method="jnp")
    np.testing.assert_array_equal(np.asarray(p), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(p2), np.asarray(ref))
    dispatch._reset_fused_fallback_warning()


def test_fused_uses_int64_key_under_x64():
    import jax

    rows = np.array([0, 5, 3, 5], np.int32)
    cols = np.array([1, 0, 2, 0], np.int32)
    M = N = 46341
    dispatch._reset_fused_fallback_warning()
    import warnings as _w
    with jax.enable_x64(), _w.catch_warnings():
        _w.simplefilter("error", RuntimeWarning)  # no fallback warning
        p = dispatch.sorted_permutation(rows, cols, M=M, N=N,
                                        method="fused")
    ref = dispatch.sorted_permutation(rows, cols, M=M, N=N, method="jnp")
    np.testing.assert_array_equal(np.asarray(p), np.asarray(ref))
