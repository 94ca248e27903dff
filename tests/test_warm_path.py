"""The warm path in front of the plan LRU: a request whose raw index
vectors are byte-identical to ones already validated skips expansion,
validation, the index upload and the key (``matlab._lookup_values``)."""
import sys
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.sparse import PlanService, fsparse, plan_cache_clear, sparse2
from repro.sparse.matlab import _ALIAS_SAMPLE, _PLAN_CACHE, alias_cache_info

L, M, N = 600, 40, 30
DTYPES = [np.int32, np.int64, np.float64]
#: an element no alias key samples, so only the full compare sees it
UNSAMPLED = 1
assert UNSAMPLED not in np.linspace(0, L - 1, _ALIAS_SAMPLE, dtype=np.intp)


@pytest.fixture(autouse=True)
def fresh_caches():
    plan_cache_clear()
    yield
    plan_cache_clear()


def _indices(dtype, seed=3):
    rng = np.random.default_rng(seed)
    return (rng.integers(1, M + 1, L).astype(dtype),
            rng.integers(1, N + 1, L).astype(dtype))


def _values(seed):
    return np.random.default_rng([seed, 1]).random(L)


def _same_matrix(S, R):
    for f in ("data", "indices", "indptr", "nnz"):
        assert np.array_equal(np.asarray(getattr(S, f)),
                              np.asarray(getattr(R, f))), f
    assert S.shape == R.shape


@pytest.mark.parametrize("shape", [(M, N), None])
@pytest.mark.parametrize("dtype", DTYPES)
def test_hit_bit_identical_to_fsparse(dtype, shape):
    ii, jj = _indices(dtype)
    svc = PlanService()
    svc.assemble(ii, jj, _values(0), shape)
    for k in (1, 2):
        S = svc.assemble(ii, jj, _values(k), shape)
        _same_matrix(S, fsparse(ii, jj, _values(k), shape))
    assert svc.stats()["alias"]["hits"] == 2


@pytest.mark.parametrize("dtype", DTYPES)
def test_scalar_values_on_hit(dtype):
    ii, jj = _indices(dtype)
    svc = PlanService()
    svc.assemble(ii, jj, _values(0), (M, N))
    S = svc.assemble(ii, jj, 2.5, (M, N))
    assert svc.stats()["alias"]["hits"] == 1
    _same_matrix(S, fsparse(ii, jj, 2.5, (M, N)))


@pytest.mark.parametrize("dtype", DTYPES)
def test_wrong_length_values_raise_as_on_a_miss(dtype):
    ii, jj = _indices(dtype)
    short = _values(0)[:-1]
    with pytest.raises(ValueError, match="same length") as miss:
        sparse2(ii, jj, short, (M, N))
    sparse2(ii, jj, _values(0), (M, N))
    with pytest.raises(ValueError, match="same length") as hit:
        sparse2(ii, jj, short, (M, N))
    assert str(hit.value) == str(miss.value)
    assert alias_cache_info()["hits"] == 1   # raised on the warm path


_CHANGES = [("valid", d) for d in DTYPES] + [
    (c, d) for c in ("zero", "negative") for d in DTYPES] + [
    (c, np.float64) for c in ("fraction", "nan")]


@pytest.mark.parametrize("which", ["ii", "jj"])
@pytest.mark.parametrize("change,dtype", _CHANGES)
def test_index_changed_in_place_is_never_served_stale(change, dtype, which):
    ii, jj = _indices(dtype)
    svc = PlanService()
    svc.assemble(ii, jj, _values(0), (M, N))
    svc.assemble(ii, jj, _values(1), (M, N))
    vec = ii if which == "ii" else jj
    vec[UNSAMPLED] = {"valid": vec[UNSAMPLED] % 5 + 1, "zero": 0,
                      "negative": -3, "fraction": 2.5,
                      "nan": np.nan}[change]
    if change != "valid":
        with pytest.raises(ValueError, match="must be positive integers"):
            svc.assemble(ii, jj, _values(2), (M, N))
    else:
        S = svc.assemble(ii, jj, _values(2), (M, N))
        _same_matrix(S, fsparse(ii, jj, _values(2), (M, N)))
        assert svc.stats()["plan"]["size"] == 2   # a new plan
    assert svc.stats()["alias"]["hits"] == 1


@pytest.mark.parametrize("first,second", [(np.int32, np.float64),
                                          (np.float64, np.int32)])
def test_dtypes_of_one_structure_share_one_plan(first, second):
    svc = PlanService()
    for dtype in (first, second, first, second):
        ii, jj = _indices(dtype)
        S = svc.assemble(ii, jj, _values(0), (M, N))
    _same_matrix(S, fsparse(ii, jj, _values(0), (M, N)))
    assert svc.stats()["plan"]["size"] == 1
    assert svc.stats()["plan"]["misses"] == 1
    alias = svc.stats()["alias"]
    assert (alias["size"], alias["hits"], alias["misses"]) == (2, 2, 2)


@pytest.mark.parametrize("dtype", DTYPES)
def test_evicted_plan_is_replanned(dtype):
    ii, jj = _indices(dtype)
    other_i, other_j = _indices(dtype, seed=4)
    svc = PlanService()
    capacity = _PLAN_CACHE.info()["capacity"]
    _PLAN_CACHE.resize(1)
    try:
        svc.assemble(ii, jj, _values(0), (M, N))
        svc.assemble(other_i, other_j, _values(0), (M, N))  # evicts it
        S = svc.assemble(ii, jj, _values(1), (M, N))
    finally:
        _PLAN_CACHE.resize(capacity)
    _same_matrix(S, fsparse(ii, jj, _values(1), (M, N)))
    assert svc.stats()["plan"]["misses"] == 3
    assert svc.stats()["alias"]["hits"] == 0


@pytest.mark.parametrize("dtype", DTYPES)
def test_stats_count_alias_hits_and_misses(dtype):
    ii, jj = _indices(dtype)
    svc = PlanService()
    assert svc.stats()["alias"]["hits"] == svc.stats()["alias"]["misses"] == 0
    for k in range(4):
        svc.assemble(ii, jj, _values(k), (M, N))
    alias = svc.stats()["alias"]
    assert (alias["size"], alias["hits"], alias["misses"]) == (1, 3, 1)
    assert alias["capacity"] == _PLAN_CACHE.info()["capacity"]


@pytest.mark.parametrize("dtype", DTYPES)
def test_sparse2_hit(dtype):
    ii, jj = _indices(dtype)
    sparse2(ii, jj, _values(0), (M, N))
    S = sparse2(ii, jj, _values(1), (M, N))
    assert alias_cache_info()["hits"] == 1
    _same_matrix(S, fsparse(ii, jj, _values(1), (M, N)))


@pytest.mark.parametrize("dtype", DTYPES)
def test_assemble_many_hits_batch_together(dtype):
    ii, jj = _indices(dtype)
    svc = PlanService()
    svc.assemble(ii, jj, _values(0), (M, N))
    outs = svc.assemble_many([(ii, jj, _values(k), (M, N))
                              for k in (1, 2, 3)])
    assert svc.stats()["alias"]["hits"] == 3
    for k, S in zip((1, 2, 3), outs):
        _same_matrix(S, fsparse(ii, jj, _values(k), (M, N)))


@pytest.mark.parametrize("ss", ["column", "row", "grid", "scalar"])
def test_outer_product_values_on_hit(ss):
    ii = np.arange(1, 7, dtype=np.int32).reshape(-1, 1)
    jj = np.array([[2, 4, 1, 4]], dtype=np.int32)
    vals = {"column": np.arange(6.0).reshape(-1, 1) + 1,
            "row": np.arange(4.0).reshape(1, -1) + 1,
            "grid": np.arange(24.0).reshape(6, 4),
            "scalar": 3.0}[ss]
    sparse2(ii, jj, np.ones((6, 4)))
    S = sparse2(ii, jj, vals)
    assert alias_cache_info()["hits"] == 1
    _same_matrix(S, fsparse(ii, jj, vals))
    with pytest.raises(ValueError, match="cannot expand"):
        sparse2(ii, jj, np.ones((4, 6)))


@pytest.mark.parametrize("args", [dict(nzmax=L + 5), dict(accum="max"),
                                  dict(format="bsr", block=2)])
def test_arguments_are_part_of_the_alias(args):
    ii, jj = _indices(np.int32)
    sparse2(ii, jj, _values(0), (M, N))
    S = sparse2(ii, jj, _values(1), (M, N), **args)
    assert alias_cache_info()["hits"] == 0
    R = fsparse(ii, jj, _values(1), (M, N), **args)
    assert type(S) is type(R)
    assert np.array_equal(np.asarray(S.to_dense()), np.asarray(R.to_dense()))


def test_sharded_hit():
    from repro.sparse import convert

    ii, jj = _indices(np.int32)
    sparse2(ii, jj, _values(0), (M, N), method="sharded")
    S = sparse2(ii, jj, _values(1), (M, N), method="sharded")
    assert alias_cache_info()["hits"] == 1
    _same_matrix(convert(S, "csc"),
                 convert(fsparse(ii, jj, _values(1), (M, N),
                                 method="sharded"), "csc"))


def test_mesh_without_sharded_method_still_raises_when_warm():
    ii, jj = _indices(np.int32)
    sparse2(ii, jj, _values(0), (M, N))
    with pytest.raises(ValueError, match="sharded"):
        sparse2(ii, jj, _values(1), (M, N), mesh=object())


def test_warm_values_are_float32_on_the_device():
    ii, jj = _indices(np.int32)
    svc = PlanService()
    for k in range(2):
        S = svc.assemble(ii, jj, _values(k).astype(np.float32), (M, N))
        assert S.data.dtype == jnp.float32


def test_concurrent_callers_under_eviction_stay_bit_identical():
    """16 threads, 3 structures in 2 dtypes each, a plan LRU of 2: hits,
    misses, evictions and stale aliases interleave on shared stores."""
    structures = [_indices(d, seed=s) for s in (5, 6, 7)
                  for d in (np.int32, np.float64)]
    refs = [fsparse(ii, jj, _values(0), (M, N)) for ii, jj in structures]
    svc = PlanService()
    errors, rounds, n_threads = [], 6, 16
    barrier = threading.Barrier(n_threads)

    def worker(t):
        try:
            barrier.wait(timeout=60)
            for r in range(rounds):
                s = (t + r) % len(structures)
                ii, jj = structures[s]
                _same_matrix(svc.assemble(ii, jj, _values(0), (M, N)),
                             refs[s])
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    capacity = _PLAN_CACHE.info()["capacity"]
    interval = sys.getswitchinterval()
    _PLAN_CACHE.resize(2)
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
        _PLAN_CACHE.resize(capacity)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    alias = svc.stats()["alias"]
    assert alias["hits"] + alias["misses"] == n_threads * rounds
