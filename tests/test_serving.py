"""Serving subsystem: locked LRU core, PlanService, persistence.

Covers the serving contracts end to end: the thread-safe LRU the plan/
product caches now ride (metrics, eviction, env-var capacity, the
first-insert-wins race rule), concurrent-access stress on the global
caches (no lost entries, bit-identical results), the AOT executable
tier (bit-identical to uncached ``fsparse``/``ops.matmul`` dispatch),
request batching, and the persistent warm-restart layer (round-trip,
no re-planning, corrupt entries degrade to a re-plan).
"""
from __future__ import annotations

import pickle
import threading
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.csc import spmv as csc_spmv
from repro.sparse import (
    LRUCache,
    PlanService,
    cached_product_plan,
    fsparse,
    ops,
    plan_cache_clear,
    plan_cache_info,
    product_cache_clear,
    product_cache_info,
    sparse2,
)
from repro.sparse.lru import env_capacity
from repro.sparse.ops import spmv_impl
from repro.sparse.serving import (
    apply_runtime_env,
    load_caches,
    runtime_env,
    save_caches,
    tcmalloc_hint,
)


@pytest.fixture(autouse=True)
def _fresh_caches():
    """Serving metrics assertions need clean global caches."""
    plan_cache_clear()
    product_cache_clear()
    yield
    plan_cache_clear()
    product_cache_clear()


def _triplet(n: int, L: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    ii = rng.integers(1, n + 1, L)
    jj = rng.integers(1, n + 1, L)
    ss = rng.normal(size=L).astype(np.float32)
    return ii, jj, ss


def _assert_same_csc(A, B):
    np.testing.assert_array_equal(np.asarray(A.indptr), np.asarray(B.indptr))
    np.testing.assert_array_equal(np.asarray(A.indices),
                                  np.asarray(B.indices))
    np.testing.assert_array_equal(np.asarray(A.data), np.asarray(B.data))
    assert int(A.nnz) == int(B.nnz) and A.shape == B.shape


# ---------------------------------------------------------------------------
# LRU core
# ---------------------------------------------------------------------------
def test_lru_eviction_order_and_recency_bump():
    c = LRUCache(2)
    c.insert("a", 1)
    c.insert("b", 2)
    assert c.get("a") == 1          # bump: a is now most-recent
    c.insert("c", 3)                # evicts b, not a
    assert "a" in c and "c" in c and "b" not in c
    assert c.info()["evictions"] == 1


def test_lru_metrics_counters():
    c = LRUCache(4)
    assert c.get("missing") is None
    c.insert("k", "v")
    assert c.get("k") == "v"
    info = c.info()
    assert info == {"size": 1, "capacity": 4, "hits": 1, "misses": 1,
                    "evictions": 0, "insertions": 1}
    c.clear()
    info = c.info()
    assert info["size"] == 0 and info["hits"] == 0 and info["misses"] == 0


def test_lru_first_insert_wins():
    c = LRUCache(4)
    first = object()
    second = object()
    assert c.insert("k", first) is first
    # a losing racer adopts the existing value, no double insertion
    assert c.insert("k", second) is first
    assert c.info()["insertions"] == 1
    assert c.get_or_create("k", lambda: second) is first


def test_lru_get_or_create_runs_factory_once_per_key():
    c = LRUCache(4)
    calls = []
    for _ in range(3):
        c.get_or_create("k", lambda: calls.append(1) or "v")
    assert len(calls) == 1
    assert c.info() == {"size": 1, "capacity": 4, "hits": 2, "misses": 1,
                        "evictions": 0, "insertions": 1}


def test_lru_resize_shrinks_lru_first():
    c = LRUCache(4)
    for k in "abcd":
        c.insert(k, k)
    c.get("a")
    c.resize(2)
    assert len(c) == 2
    assert "a" in c and "d" in c   # the two most recently used survive
    with pytest.raises(ValueError):
        c.resize(0)


def test_lru_env_capacity(monkeypatch):
    assert env_capacity(None, 7) == 7
    monkeypatch.delenv("REPRO_PLAN_CACHE_SIZE", raising=False)
    assert env_capacity("REPRO_PLAN_CACHE_SIZE", 7) == 7
    monkeypatch.setenv("REPRO_PLAN_CACHE_SIZE", "3")
    assert LRUCache(7, env="REPRO_PLAN_CACHE_SIZE").info()["capacity"] == 3
    monkeypatch.setenv("REPRO_PLAN_CACHE_SIZE", "zero")
    with pytest.raises(ValueError, match="not an integer"):
        LRUCache(7, env="REPRO_PLAN_CACHE_SIZE")
    monkeypatch.setenv("REPRO_PLAN_CACHE_SIZE", "0")
    with pytest.raises(ValueError, match=">= 1"):
        LRUCache(7, env="REPRO_PLAN_CACHE_SIZE")


@pytest.mark.parametrize("sanitize", [False, True])
def test_lru_concurrent_no_lost_entries(sanitize):
    c = LRUCache(64, sanitize=sanitize)
    keys = [f"k{i}" for i in range(8)]
    barrier = threading.Barrier(8)

    def worker(t):
        barrier.wait()
        for i in range(200):
            k = keys[(t + i) % len(keys)]
            v = c.get_or_create(k, lambda k=k: ("value", k))
            assert v == ("value", k)

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    info = c.info()
    assert len(c) == len(keys)
    # first-insert-wins: every key inserted exactly once, none lost
    assert info["insertions"] == len(keys)
    assert info["evictions"] == 0
    assert info["hits"] + info["misses"] == 8 * 200
    if sanitize:
        # clean stress run: lock tracking on, zero discipline findings
        assert info["lock_sanitize"] is True
        assert info["lock_reentries"] == 0
    else:
        assert "lock_sanitize" not in info   # default dict shape intact


def test_lock_sanitizer_flags_factory_under_lock():
    """Hold-across-plan detection: a get_or_create miss while the
    calling thread holds the cache lock is the serialize-everything
    bug; in sanitize mode it raises a named InvariantViolation at the
    call site."""
    from repro.sparse import InvariantViolation

    c = LRUCache(4, name="sanitized", sanitize=True)
    with pytest.raises(InvariantViolation, match="lock-discipline") as ei:
        with c._locked():
            c.get_or_create("k", lambda: 1)
    assert ei.value.invariant == "lock-discipline"
    # outside the lock the same call is fine, and re-entries were counted
    assert c.get_or_create("k", lambda: 1) == 1
    assert c.info()["lock_reentries"] == 1

    # sanitize off (the default): no tracking, no false positives
    c2 = LRUCache(4)
    with c2._locked():
        assert c2.get_or_create("k", lambda: 2) == 2
    assert not c2.holds_lock()


def test_env_lock_sanitize(monkeypatch):
    monkeypatch.setenv("REPRO_LOCK_SANITIZE", "1")
    assert LRUCache(2).info()["lock_sanitize"] is True
    monkeypatch.setenv("REPRO_LOCK_SANITIZE", "0")
    assert "lock_sanitize" not in LRUCache(2).info()


# ---------------------------------------------------------------------------
# Concurrent stress on the real global caches
# ---------------------------------------------------------------------------
def test_sparse2_concurrent_stress_bit_identical():
    n, L = 50, 400
    structures = [_triplet(n, L, seed=s) for s in range(4)]
    refs = [sparse2(ii, jj, ss, (n, n)) for ii, jj, ss in structures]
    plan_cache_clear()

    errors = []
    barrier = threading.Barrier(8)

    def worker(t):
        try:
            barrier.wait()
            for i in range(12):
                s = (t + i) % len(structures)
                ii, jj, ss = structures[s]
                A = sparse2(ii, jj, ss, (n, n))
                _assert_same_csc(A, refs[s])
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    info = plan_cache_info()
    assert info["size"] == len(structures)          # no lost entries
    assert info["insertions"] == len(structures)    # each planned once
    assert info["hits"] + info["misses"] == 8 * 12


def test_cached_product_plan_concurrent_stress():
    n = 40
    pairs = []
    for s in range(3):
        ii, jj, ss = _triplet(n, 200, seed=10 + s)
        kk, ll, tt = _triplet(n, 200, seed=20 + s)
        pairs.append((fsparse(ii, jj, ss, (n, n)),
                      fsparse(kk, ll, tt, (n, n))))
    product_cache_clear()

    got: list = [[] for _ in pairs]
    errors = []
    barrier = threading.Barrier(6)

    def worker(t):
        try:
            barrier.wait()
            for i in range(8):
                s = (t + i) % len(pairs)
                A, B = pairs[s]
                got[s].append(cached_product_plan(A, B))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    info = product_cache_info()
    assert info["size"] == len(pairs)
    assert info["insertions"] == len(pairs)
    # every caller got THE cached plan object (losers adopt the winner)
    for plans in got:
        assert len({id(p) for p in plans}) == 1


# ---------------------------------------------------------------------------
# PlanService: AOT executables bit-identical to uncached dispatch
# ---------------------------------------------------------------------------
def test_service_assemble_matches_fsparse():
    n, L = 60, 500
    ii, jj, ss = _triplet(n, L)
    svc = PlanService()
    A = svc.assemble(ii, jj, ss, (n, n))
    _assert_same_csc(A, fsparse(ii, jj, ss, (n, n)))
    # second request: plan hit + executable hit, still identical
    A2 = svc.assemble(ii, jj, ss * 2, (n, n))
    _assert_same_csc(A2, fsparse(ii, jj, ss * 2, (n, n)))
    st = svc.stats()
    assert st["plan"]["hits"] >= 1
    assert st["exec"] == {"size": 1, "capacity": 64, "hits": 1,
                          "misses": 1, "evictions": 0, "insertions": 1}


def test_service_assemble_accum_modes():
    ii = np.array([1, 1, 2, 3, 1])
    jj = np.array([1, 1, 2, 3, 1])
    ss = np.array([5.0, -2.0, 3.0, 4.0, 1.0], np.float32)
    svc = PlanService()
    for accum in ("sum", "min", "max", "mean", "first", "last"):
        A = svc.assemble(ii, jj, ss, (3, 3), accum=accum)
        _assert_same_csc(A, sparse2(ii, jj, ss, (3, 3), accum=accum))


def test_service_multiply_matches_ops_matmul():
    n = 50
    ii, jj, ss = _triplet(n, 300, seed=1)
    kk, ll, tt = _triplet(n, 300, seed=2)
    A = fsparse(ii, jj, ss, (n, n))
    B = fsparse(kk, ll, tt, (n, n))
    svc = PlanService()
    C = svc.multiply(A, B)
    _assert_same_csc(C, ops.matmul(A, B))
    C2 = svc.multiply(A, B)   # executable replay
    _assert_same_csc(C2, C)
    assert svc.stats()["exec"]["hits"] == 1


def test_service_spmv_matches_uncached_dispatch():
    n = 64
    ii, jj, ss = _triplet(n, 400, seed=3)
    S = fsparse(ii, jj, ss, (n, n))
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=n).astype(np.float32))
    svc = PlanService()
    y = svc.spmv(S, x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(csc_spmv(S, x)))
    # dense-matrix right-hand side: vmapped executable vs eager columns
    X = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    Y = svc.spmv(S, X)
    fn, Sr = spmv_impl(S)
    ref = jnp.stack([fn(Sr, X[:, j]) for j in range(3)], axis=1)
    np.testing.assert_array_equal(np.asarray(Y), np.asarray(ref))
    with pytest.raises(ValueError, match="vector or matrix"):
        svc.spmv(S, jnp.ones((2, 2, 2)))


def test_service_spmv_symcsc_and_bsr_aot_equals_jit():
    """The AOT executable tier handles multi-field formats: SymCSC
    (diag + data rebind) and BSR (block in the executable key) must
    replay from cache and match the eager per-format dispatch."""
    from repro.sparse.formats import BSR, SymCSC, convert
    from repro.sparse.ops import matmul as ops_matmul

    n = 32
    rng = np.random.default_rng(21)
    r0 = rng.integers(1, n + 1, 100)
    c0 = rng.integers(1, n + 1, 100)
    ii = np.concatenate([r0, c0])
    jj = np.concatenate([c0, r0])
    S = fsparse(ii, jj, np.ones(len(ii), np.float32), (n, n))
    Y = convert(S, "symcsc")
    assert isinstance(Y, SymCSC)
    B = convert(fsparse([1, 2, 3, 4], [1, 2, 3, 4],
                        np.arange(1.0, 5.0), (4, 4)), "bsr", block=2)
    assert isinstance(B, BSR)

    svc = PlanService()
    x = jnp.asarray(rng.integers(0, 4, n).astype(np.float32))
    y_aot = svc.spmv(Y, x)
    np.testing.assert_array_equal(np.asarray(y_aot),
                                  np.asarray(ops_matmul(Y, x)))
    # same structure again: pure executable replay
    h0 = svc.stats()["exec"]["hits"]
    np.testing.assert_array_equal(np.asarray(svc.spmv(Y, x)),
                                  np.asarray(y_aot))
    assert svc.stats()["exec"]["hits"] == h0 + 1

    xb = jnp.asarray(rng.integers(0, 4, 4).astype(np.float32))
    yb = svc.spmv(B, xb)
    np.testing.assert_array_equal(np.asarray(yb),
                                  np.asarray(ops_matmul(B, xb)))


def test_service_assemble_many_groups_and_preserves_order():
    n = 40
    ii_a, jj_a, ss_a = _triplet(n, 300, seed=4)
    ii_b, jj_b, ss_b = _triplet(n, 200, seed=5)
    svc = PlanService()
    reqs = [
        (ii_a, jj_a, ss_a, (n, n)),
        (ii_b, jj_b, ss_b, (n, n)),
        (ii_a, jj_a, ss_a * 2, (n, n)),
        (ii_a, jj_a, ss_a - 1, (n, n)),
    ]
    out = svc.assemble_many(reqs)
    assert len(out) == 4
    _assert_same_csc(out[0], fsparse(ii_a, jj_a, ss_a, (n, n)))
    _assert_same_csc(out[1], fsparse(ii_b, jj_b, ss_b, (n, n)))
    _assert_same_csc(out[2], fsparse(ii_a, jj_a, ss_a * 2, (n, n)))
    _assert_same_csc(out[3], fsparse(ii_a, jj_a, ss_a - 1, (n, n)))
    # one batched executable (B=3) + one singleton executable
    exec_info = svc.stats()["exec"]
    assert exec_info["size"] == 2 and exec_info["insertions"] == 2


def test_service_concurrent_requests_bit_identical():
    n, L = 50, 400
    ii, jj, ss = _triplet(n, L, seed=6)
    ref = fsparse(ii, jj, ss, (n, n))
    svc = PlanService()
    errors = []
    barrier = threading.Barrier(6)

    def worker():
        try:
            barrier.wait()
            for _ in range(6):
                _assert_same_csc(svc.assemble(ii, jj, ss, (n, n)), ref)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert svc.stats()["exec"]["size"] == 1


def test_service_donate_defaults_off_on_cpu():
    svc = PlanService()
    if jax.default_backend() == "cpu":
        assert svc.donate is False
    assert PlanService(donate=True).donate is True


# ---------------------------------------------------------------------------
# The fill executable belongs to a shape: the plan is an argument
# ---------------------------------------------------------------------------
def test_service_fill_executable_shared_across_structures():
    n, L = 60, 500
    svc = PlanService()
    for seed in (41, 42):   # two structures of equal (L, nzmax)
        ii, jj, ss = _triplet(n, L, seed=seed)
        _assert_same_csc(svc.assemble(ii, jj, ss, (n, n), L),
                         fsparse(ii, jj, ss, (n, n), L))
    info = svc.stats()["exec"]
    assert info["insertions"] == 1 and info["size"] == 1
    assert info["hits"] == 1


@pytest.mark.parametrize("batched", [False, True])
def test_served_fill_lowers_no_plan_sized_constant(batched):
    """The lowered program does not grow with L: nothing of the plan
    is embedded in it."""
    from repro.sparse import plan
    from repro.sparse.serving import _served_fill

    def text(L):
        rng = np.random.default_rng(L)
        pat = plan(jnp.asarray(rng.integers(0, 300, L), jnp.int32),
                   jnp.asarray(rng.integers(0, 300, L), jnp.int32),
                   (300, 300))
        vals = (3, L) if batched else (L,)
        return _served_fill(pat, batched=batched).lower(
            pat.perm, pat.slot, jax.ShapeDtypeStruct(vals, jnp.float32),
        ).as_text()

    assert len(text(100_000)) < 2 * len(text(10_000))


def test_service_donation_keeps_the_plan_arrays():
    from repro.sparse.matlab import _lookup_values

    n, L = 50, 400
    ii, jj, ss = _triplet(n, L, seed=43)
    svc = PlanService(donate=True)
    for k in (1, 2):
        svc.assemble(ii, jj, ss * k, (n, n))
    _, pat, _ = _lookup_values(ii, jj, ss, (n, n))
    assert not pat.perm.is_deleted() and not pat.slot.is_deleted()
    _assert_same_csc(svc.assemble(ii, jj, ss * 3, (n, n)),
                     fsparse(ii, jj, ss * 3, (n, n)))


@pytest.fixture(scope="module")
def exec_cache_spans(tmp_path_factory):
    """The ``sparse.exec_cache`` host spans of three ``assemble``
    requests on one structure (cold, warm, warm), from a CPU trace."""
    import glob

    from repro.core import spans

    ii, jj, ss = _triplet(30, 200, seed=44)
    plan_cache_clear()
    svc = PlanService()
    logdir = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        for k in range(3):
            svc.assemble(ii, jj, ss + k, (30, 30)).data.block_until_ready()
    finally:
        jax.profiler.stop_trace()
        plan_cache_clear()
    (path,) = glob.glob(str(logdir / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    events = sorted(
        (e.start_ns, dict(e.stats))
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name == spans.EXEC_CACHE)
    return [stats for _, stats in events]


@pytest.mark.parametrize("request_no,hit", [(0, 0), (1, 1), (2, 1)])
def test_exec_cache_span_carries_hit(exec_cache_spans, request_no, hit):
    assert len(exec_cache_spans) == 3
    assert exec_cache_spans[request_no]["hit"] == hit


# ---------------------------------------------------------------------------
# update_structure: delta absorption without a cache flush (ISSUE 7)
# ---------------------------------------------------------------------------
def _delta(n: int, Ld: int, seed: int = 100):
    rng = np.random.default_rng(seed)
    return (rng.integers(1, n + 1, Ld), rng.integers(1, n + 1, Ld),
            rng.normal(size=Ld).astype(np.float32))


def test_service_update_structure_matches_cold_assemble():
    n, L, Ld = 40, 300, 30
    ii, jj, ss = _triplet(n, L, seed=30)
    ai, aj, av = _delta(n, Ld, seed=31)
    rng = np.random.default_rng(32)
    dm = np.zeros(L, bool)
    dm[rng.choice(L, 20, replace=False)] = True

    svc = PlanService()
    svc.assemble(ii, jj, ss, (n, n), L + Ld)  # warm (with headroom)
    U = svc.update_structure(ii, jj, ss, ai, aj, av, (n, n), L + Ld,
                             drop_mask=dm)
    keep = ~dm
    ref = fsparse(np.concatenate([ii[keep], ai]),
                  np.concatenate([jj[keep], aj]),
                  np.concatenate([ss[keep], av]), (n, n), nzmax=L + Ld)
    _assert_same_csc(U, ref)


def test_service_update_retires_only_affected_executables():
    """The acceptance pin: a warm service absorbs a structural delta
    by retiring exactly the updated structure's executables — the other
    tenants' fills/spmvs keep replaying from cache (hits, no new
    lowering)."""
    n, cap = 40, 325
    ii_a, jj_a, ss_a = _triplet(n, 300, seed=33)
    ii_b, jj_b, ss_b = _triplet(n, 200, seed=34)
    ai, aj, av = _delta(n, 25, seed=35)

    svc = PlanService()
    svc.assemble(ii_a, jj_a, ss_a, (n, n), cap)  # exec 1: fill A
    B = svc.assemble(ii_b, jj_b, ss_b, (n, n))   # exec 2: fill B
    x = jnp.ones(n, jnp.float32)
    svc.spmv(B, x)                               # exec 3: spmv on B
    before = svc.stats()["exec"]
    assert before["size"] == 3 and before["insertions"] == 3

    svc.update_structure(ii_a, jj_a, ss_a, ai, aj, av, (n, n), cap)
    mid = svc.stats()["exec"]
    # fill executables belong to a shape, not a structure: A's stays
    # (another structure of its shape may use it), the updated shape's
    # fill is lowered once: one more entry, one more insertion, no
    # evictions
    assert mid["size"] == 4
    assert mid["insertions"] == before["insertions"] + 1
    assert mid["evictions"] == 0

    # B's executables were untouched: replays are pure hits
    svc.assemble(ii_b, jj_b, ss_b * 3, (n, n))
    svc.spmv(B, x)
    after = svc.stats()["exec"]
    assert after["insertions"] == mid["insertions"]   # nothing re-lowered
    assert after["hits"] >= mid["hits"] + 2

    # a repeated identical update replays the updated fill from cache
    svc.update_structure(ii_a, jj_a, ss_a, ai, aj, av, (n, n), cap)
    final = svc.stats()["exec"]
    assert final["insertions"] == after["insertions"]
    assert final["size"] == 4


def test_service_update_retires_spgemm_executables_and_products():
    n, cap = 36, 270
    ii, jj, ss = _triplet(n, 250, seed=36)
    kk, ll, tt = _triplet(n, 250, seed=37)
    ai, aj, av = _delta(n, 20, seed=38)
    svc = PlanService()
    A = svc.assemble(ii, jj, ss, (n, n), cap)
    B = fsparse(kk, ll, tt, (n, n))
    svc.multiply(A, B)
    assert svc.stats()["exec"]["size"] == 2      # fill A + multiply
    assert product_cache_info()["size"] == 1

    svc.update_structure(ii, jj, ss, ai, aj, av, (n, n), cap)
    # multiply executable referenced A's old structure: retired; the
    # fills of the old and the updated shape both stay
    ekinds = sorted(k[0] for k, _ in svc._execs.items())
    assert ekinds == ["fill", "fill"]
    # dependent product plan purged lazily at the next product lookup
    A0 = fsparse(ii, jj, ss, (n, n), nzmax=cap)
    C2 = svc.multiply(A0, B)
    info = product_cache_info()
    assert info["size"] == 1
    _assert_same_csc(C2, ops.matmul(A0, B))


def test_service_update_retires_persisted_entries(
        tmp_path, private_compile_cache):
    n, cap = 32, 216
    ii, jj, ss = _triplet(n, 200, seed=39)
    ai, aj, av = _delta(n, 16, seed=40)
    svc = PlanService(cache_dir=tmp_path)
    svc.assemble(ii, jj, ss, (n, n), cap)
    assert len(list(tmp_path.glob("plan-*.pkl"))) == 1

    U = svc.update_structure(ii, jj, ss, ai, aj, av, (n, n), cap)
    # old plan unlinked, updated plan persisted: still exactly one file
    assert len(list(tmp_path.glob("plan-*.pkl"))) == 1

    # warm restart: the *updated* structure (addressed by its
    # concatenated stream) is served from disk with no re-planning
    plan_cache_clear()
    svc2 = PlanService(cache_dir=tmp_path)
    assert svc2.loaded_plans == 1
    U2 = svc2.assemble(np.concatenate([ii, ai]), np.concatenate([jj, aj]),
                       np.concatenate([ss, av]), (n, n), cap)
    _assert_same_csc(U2, U)
    assert plan_cache_info()["misses"] == 0


# ---------------------------------------------------------------------------
# Persistence + warm restart
# ---------------------------------------------------------------------------
def test_persistence_roundtrip_and_warm_restart(
        tmp_path, private_compile_cache):
    n = 48
    ii, jj, ss = _triplet(n, 300, seed=8)
    kk, ll, tt = _triplet(n, 300, seed=9)
    A = fsparse(ii, jj, ss, (n, n))
    B = fsparse(kk, ll, tt, (n, n))

    svc = PlanService(cache_dir=tmp_path)
    assert svc.loaded_plans == 0 and svc.loaded_products == 0
    S = svc.assemble(ii, jj, ss, (n, n))
    C = svc.multiply(A, B)
    assert list(tmp_path.glob("plan-*.pkl"))
    assert list(tmp_path.glob("product-*.pkl"))

    # "restart": wipe the in-memory caches, reload from disk
    plan_cache_clear()
    product_cache_clear()
    svc2 = PlanService(cache_dir=tmp_path)
    assert svc2.loaded_plans == 1 and svc2.loaded_products == 1
    S2 = svc2.assemble(ii, jj, ss, (n, n))
    C2 = svc2.multiply(A, B)
    _assert_same_csc(S2, S)
    _assert_same_csc(C2, C)
    # the restart contract: nothing was re-planned
    assert plan_cache_info()["misses"] == 0
    assert product_cache_info()["misses"] == 0


def test_save_caches_flushes_existing_entries(tmp_path):
    n = 32
    ii, jj, ss = _triplet(n, 200, seed=11)
    sparse2(ii, jj, ss, (n, n))          # populate the global plan LRU
    assert save_caches(tmp_path) == 1
    plan_cache_clear()
    assert load_caches(tmp_path) == (1, 0)
    _assert_same_csc(sparse2(ii, jj, ss, (n, n)),
                     fsparse(ii, jj, ss, (n, n)))
    assert plan_cache_info()["misses"] == 0


def test_corrupt_cache_entry_degrades_to_replan(
        tmp_path, private_compile_cache):
    n = 32
    ii, jj, ss = _triplet(n, 200, seed=12)
    svc = PlanService(cache_dir=tmp_path)
    svc.assemble(ii, jj, ss, (n, n))
    (tmp_path / "plan-deadbeef.pkl").write_bytes(b"not a pickle")
    (tmp_path / "plan-feedface.pkl").write_bytes(
        pickle.dumps({"wrong": "schema"}))
    plan_cache_clear()
    with pytest.warns(RuntimeWarning, match="unreadable plan-cache entry"):
        svc2 = PlanService(cache_dir=tmp_path)
    assert svc2.loaded_plans == 1      # the good entry still loads
    _assert_same_csc(svc2.assemble(ii, jj, ss, (n, n)),
                     fsparse(ii, jj, ss, (n, n)))


def test_service_save_requires_cache_dir():
    with pytest.raises(ValueError, match="no cache_dir"):
        PlanService().save()


# ---------------------------------------------------------------------------
# Runtime env helpers + re-exports
# ---------------------------------------------------------------------------
def test_apply_runtime_env_merges_not_clobbers(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_foo=1")
    monkeypatch.setenv("TF_CPP_MIN_LOG_LEVEL", "0")
    monkeypatch.delenv("TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD",
                       raising=False)
    applied = apply_runtime_env()
    import os
    assert "--xla_foo=1" in os.environ["XLA_FLAGS"]
    for flag in runtime_env()["XLA_FLAGS"].split():
        assert flag.split("=")[0] in os.environ["XLA_FLAGS"]
    assert os.environ["TF_CPP_MIN_LOG_LEVEL"] == "0"   # user wins
    assert "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD" in applied
    # idempotent: a second call changes nothing
    assert apply_runtime_env() == {}


def test_tcmalloc_hint_shape(monkeypatch):
    monkeypatch.setenv("LD_PRELOAD", "/usr/lib/libtcmalloc.so.4")
    assert tcmalloc_hint() is None     # already preloaded
    monkeypatch.setenv("LD_PRELOAD", "")
    hint = tcmalloc_hint()
    assert hint is None or hint.startswith("LD_PRELOAD=")


def test_serve_namespace_reexports_serving_api():
    import repro.serve as serve

    for name in ("PlanService", "apply_runtime_env", "runtime_env",
                 "save_caches", "load_caches", "enable_compilation_cache",
                 "tcmalloc_hint", "prefill", "decode_step", "init_cache"):
        assert hasattr(serve, name), name
        assert name in serve.__all__


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_dir_follows_env_else_checkout(
        env_set, tmp_path, monkeypatch, private_compile_cache):
    checkout_cache = Path(__file__).resolve().parents[1] / ".jax_cache"
    if env_set:
        # the variable is JAX's to read: the service sets no directory
        PlanService(cache_dir=tmp_path / "plans")
        assert jax.config.jax_compilation_cache_dir == private_compile_cache
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        PlanService(cache_dir=tmp_path / "plans")
        assert jax.config.jax_compilation_cache_dir == str(checkout_cache)
    # plan pickles stay in cache_dir; no compile cache is put there
    assert not (tmp_path / "plans" / "xla").exists()


def test_cache_info_keeps_historical_keys():
    info = plan_cache_info()
    for k in ("size", "capacity", "hits", "misses", "evictions",
              "insertions"):
        assert k in info
    info = product_cache_info()
    for k in ("size", "capacity", "hits", "misses", "evictions",
              "insertions"):
        assert k in info
