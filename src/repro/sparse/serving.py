"""Serving-scale plan service: AOT executables + persistent warm restarts.

The paper's §2.3 thesis — run the expensive symbolic analysis once,
replay the cheap numeric fill many times — becomes *cache
infrastructure* at serving scale: a process handling concurrent request
streams for many tenants must (a) share symbolic plans across threads
without corruption, (b) stop paying jit re-trace/re-compile per request
once a structure is hot, and (c) come back warm after a restart.  This
module is that layer, sitting between the plan/fill core and callers:

* **One locked cache core** (:mod:`repro.sparse.lru`): the ``sparse2``
  plan LRU, the SpGEMM product LRU and the executable tier below all
  ride the same thread-safe, metrics-instrumented implementation.
* **AOT executable tier**: per shape, the numeric fill is lowered and
  compiled **once** (``jax.jit(fill).lower(spec).compile()``), with
  the plan's ``perm``/``slot`` as arguments, and the compiled
  executable is replayed for every request on every structure of that
  shape — no python re-trace, no jit-cache hashing of a pytree plan
  per call.  Value buffers are donated on backends that support
  donation (GPU/TPU), so a request's input buffer is recycled into the
  output.  Covered ops: fill (``assemble``), batched fill
  (``assemble_many``), SpGEMM (``multiply``) and SpMV (``spmv``); the
  last two are still compiled per structure, with its index arrays
  embedded.  All executables are
  lowered from exactly the code the uncached paths run, so results are
  bit-identical to ``fsparse``/``ops.matmul`` dispatch.
* **Persistent warm restarts**: plan/product cache entries are written
  through to ``cache_dir`` (one pickle of the exact cache key + the
  host-side plan pytree per entry) and loaded back on construction, so
  a restarted server re-plans **nothing**; JAX's persistent
  compilation cache is turned on too (``JAX_COMPILATION_CACHE_DIR``
  where set, else a fixed ``.jax_cache`` in the checkout), so on
  backends that support it the XLA executables are disk-cached as well.
* **Request batching**: :meth:`PlanService.assemble_many` groups
  same-structure requests from independent streams and rides one
  ``vmap``-batched fill executable across the group.

The ``custom_vjp`` caveat carries over unchanged: the fills behind
these executables exclude *forward-mode* AD (``jax.jvp``/``jax.jacfwd``
through a fill raises ``TypeError`` by JAX's design), and an AOT
executable additionally freezes the primal computation only — take
gradients through ``pattern.assemble``/``ops`` (the jit path), not
through a compiled executable.

    >>> import numpy as np, os, tempfile
    >>> from repro.sparse.serving import PlanService
    >>> from repro.sparse import plan_cache_clear
    >>> plan_cache_clear()
    >>> svc = PlanService(cache_dir=tempfile.mkdtemp())
    >>> S = svc.assemble([3, 2, 3], [1, 2, 1], [7.0, 9.0, 1.0])  # cold
    >>> S2 = svc.assemble([3, 2, 3], [1, 2, 1], [2.0, 2.0, 2.0])  # warm
    >>> info = svc.stats()["plan"]
    >>> info["misses"], info["hits"]
    (1, 1)
    >>> plan_cache_clear()                    # "restart" the process
    >>> svc2 = PlanService(cache_dir=svc.cache_dir)
    >>> svc2.loaded_plans                     # warm: plan read from disk
    1
    >>> S3 = svc2.assemble([3, 2, 3], [1, 2, 1], [7.0, 9.0, 1.0])
    >>> svc2.stats()["plan"]["misses"]        # no re-planning
    0
    >>> bool(np.array_equal(np.asarray(S3.data), np.asarray(S.data)))
    True
    >>> import jax                            # the compile cache: env var
    >>> from repro.sparse.serving import DEFAULT_COMPILATION_CACHE_DIR
    >>> jax.config.jax_compilation_cache_dir == (
    ...     os.environ.get("JAX_COMPILATION_CACHE_DIR")
    ...     or str(DEFAULT_COMPILATION_CACHE_DIR))
    True
"""
from __future__ import annotations

import dataclasses
import hashlib
import itertools
import os
import pickle
import threading
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from ..core.csc import CSC
from ..core.spans import (ASSEMBLE, ASSEMBLE_MANY, COMPILE, EXEC_CACHE, FILL,
                          MULTIPLY, SPMV, UPDATE_STRUCTURE, span)
from . import tuning
from .analysis.invariants import maybe_validate_pattern, validate_pattern
from .errors import CacheCorruptionWarning, InvariantViolation
from .formats import convert
from .lru import LRUCache
from .matlab import (alias_cache_info, plan_cache_info, plan_update,
                     _lookup_values, _PLAN_CACHE)
from .ops import matmul as _ops_matmul, spmv_impl
from .pattern import SparsePattern
from .spgemm import (
    ProductPattern,
    product_cache_info,
    product_lookup,
    _PRODUCT_CACHE,
)

__all__ = [
    "PlanService",
    "apply_runtime_env",
    "enable_compilation_cache",
    "load_caches",
    "runtime_env",
    "save_caches",
    "tcmalloc_hint",
]

#: numeric (re-bindable) fields per flat compressed format, keyed by
#: class name; everything else (e.g. sharded block formats) falls back
#: to the ordinary ``ops.matmul`` dispatch in :meth:`PlanService.spmv`.
_SPMV_NUMERIC_FIELDS = {
    "CSC": ("data",),
    "CSR": ("data",),
    "BSR": ("data",),
    "SymCSC": ("diag", "data"),
}


# ---------------------------------------------------------------------------
# Tuned serving runtime environment (olmax-style entrypoint hygiene)
# ---------------------------------------------------------------------------
_TCMALLOC_PATHS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/libtcmalloc.so.4",
)


def runtime_env() -> dict:
    """Recommended environment for a serving process.

    The knobs a tuned entrypoint script sets before python starts (cf.
    the olmax ``run.sh`` exemplar): silence tcmalloc's large-alloc
    reports (plan arrays routinely cross its default threshold), quiet
    the TF/XLA C++ log spam that would interleave with request logs,
    and pin the XLA backend optimization level so every restart of the
    server compiles executables identically (persistent-cache hits stay
    valid across deploys that inherit different ambient flags).
    Nothing here changes numerics — cached replay must stay
    bit-identical to fresh dispatch.
    """
    return {
        "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000",
        "TF_CPP_MIN_LOG_LEVEL": "2",
        "XLA_FLAGS": "--xla_backend_optimization_level=3",
    }


def apply_runtime_env() -> dict:
    """Apply :func:`runtime_env` to ``os.environ`` (non-destructively).

    Plain variables are only set when absent; ``XLA_FLAGS`` is merged
    flag-by-flag so user-provided flags survive.  Returns the mapping
    of variables actually changed.  Call this *before* the first jax
    computation — XLA reads its flags at backend initialization.
    """
    applied = {}
    for var, val in runtime_env().items():
        if var == "XLA_FLAGS":
            current = os.environ.get(var, "")
            missing = [f for f in val.split()
                       if f.split("=")[0] not in current]
            if missing:
                merged = " ".join(filter(None, [current, *missing]))
                os.environ[var] = merged
                applied[var] = merged
        elif var not in os.environ:
            os.environ[var] = val
            applied[var] = val
    return applied


def tcmalloc_hint() -> str | None:
    """``LD_PRELOAD`` line for tcmalloc, if installed but not loaded.

    Preloading cannot be done from inside a running process, so this is
    a hint for the launcher (print it, or export it in the wrapper
    script); returns ``None`` when tcmalloc is already preloaded or not
    installed.
    """
    preload = os.environ.get("LD_PRELOAD", "")
    if "tcmalloc" in preload:
        return None
    for path in _TCMALLOC_PATHS:
        if os.path.exists(path):
            return f"LD_PRELOAD={path}"
    return None


#: the compile-cache directory used when ``JAX_COMPILATION_CACHE_DIR``
#: is unset: fixed inside the checkout, so every process of every run
#: (the cache key includes the path) finds what an earlier one wrote.
DEFAULT_COMPILATION_CACHE_DIR = (
    Path(__file__).resolve().parents[3] / ".jax_cache"
)


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
    and no directory is set here; otherwise the cache goes to
    :data:`DEFAULT_COMPILATION_CACHE_DIR`.  Every executable is cached
    (no minimum compile time or entry size).  Plan persistence never
    depends on this — it lives in ``PlanService(cache_dir=...)``.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(DEFAULT_COMPILATION_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


# ---------------------------------------------------------------------------
# Persistent plan/product cache entries
# ---------------------------------------------------------------------------
_PICKLE_PROTOCOL = 4  # fixed so digests are stable across interpreters


def _entry_digest(key) -> str:
    """Stable filename digest of a cache key (keys are bytes/str/int
    tuples, so their pickling is deterministic at a fixed protocol)."""
    raw = pickle.dumps(key, protocol=_PICKLE_PROTOCOL)
    return hashlib.sha256(raw).hexdigest()[:32]


def _host_tree(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


def _device_tree(tree):
    return jax.tree_util.tree_map(jnp.asarray, tree)


def _entry_path(cache_dir: Path, kind: str, key) -> Path:
    return Path(cache_dir) / f"{kind}-{_entry_digest(key)}.pkl"


def _write_entry(cache_dir: Path, kind: str, key, value) -> Path:
    """Atomically persist one cache entry (exact key + host pytree)."""
    path = _entry_path(cache_dir, kind, key)
    if path.exists():
        return path
    payload = {"kind": kind, "key": key, "value": _host_tree(value)}
    tmp = path.with_suffix(f".tmp.{os.getpid()}.{threading.get_ident()}")
    with open(tmp, "wb") as f:
        pickle.dump(payload, f, protocol=_PICKLE_PROTOCOL)
    os.replace(tmp, path)  # atomic: concurrent writers race benignly
    return path


def save_caches(cache_dir) -> int:
    """Persist every in-memory plan/product cache entry to ``cache_dir``.

    Only host-replayable plans are persisted (:class:`SparsePattern`
    and :class:`ProductPattern`; sharded plans carry a live device mesh
    and are rebuilt per process).  Returns the number of entries on
    disk afterwards that this call wrote or refreshed.
    """
    cache_dir = Path(cache_dir)
    cache_dir.mkdir(parents=True, exist_ok=True)
    written = 0
    for kind, cache, types in (
        ("plan", _PLAN_CACHE, (SparsePattern,)),
        ("product", _PRODUCT_CACHE, (ProductPattern,)),
    ):
        for key, value in cache.items():
            if isinstance(value, types):
                _write_entry(cache_dir, kind, key, value)
                written += 1
    return written


def load_caches(cache_dir) -> tuple:
    """Load persisted entries back into the in-memory caches.

    Returns ``(plans, products)`` counts.  Corrupt/unreadable files are
    skipped with a :class:`~repro.sparse.errors.CacheCorruptionWarning`
    — a damaged cache entry must degrade to a re-plan, never to a
    crash.  Every entry that *does* unpickle is run through the
    structural validators (:mod:`repro.sparse.analysis.invariants`)
    before insertion, unconditionally: a tampered pickle that still
    deserializes is detected by the invariant it breaks, not served.
    """
    cache_dir = Path(cache_dir)
    counts = {"plan": 0, "product": 0}
    if not cache_dir.is_dir():
        return (0, 0)
    targets = {"plan": _PLAN_CACHE, "product": _PRODUCT_CACHE}
    expected = {"plan": SparsePattern, "product": ProductPattern}
    for path in sorted(cache_dir.glob("*.pkl")):
        try:
            with open(path, "rb") as f:
                payload = pickle.load(f)
            kind = payload["kind"]
            value = _device_tree(payload["value"])
            if not isinstance(value, expected[kind]):
                raise InvariantViolation(
                    "entry-schema",
                    f"{kind} entry holds a "
                    f"{type(value).__name__}, expected "
                    f"{expected[kind].__name__}",
                    subject=path.name,
                )
            validate_pattern(value, subject=path.name)
            targets[kind].insert(payload["key"], value)
            counts[kind] += 1
        except InvariantViolation as e:
            warnings.warn(
                f"skipping invalid plan-cache entry {path.name}: {e}",
                CacheCorruptionWarning,
                stacklevel=2,
            )
        except Exception as e:  # noqa: BLE001 - degrade to re-plan
            warnings.warn(
                f"skipping unreadable plan-cache entry {path.name}: "
                f"{type(e).__name__}: {e}",
                CacheCorruptionWarning,
                stacklevel=2,
            )
    return (counts["plan"], counts["product"])


def _served_fill(pat: SparsePattern, *, batched: bool = False,
                 donate: bool = False):
    """The served fill, jitted as a function of ``(perm, slot, vals)``.

    :meth:`SparsePattern.scatter` on ``pat`` with its ``perm`` and
    ``slot`` replaced by the arguments, so results are bit-identical to
    ``fsparse``.  ``pat`` lends the static geometry (``nzmax``,
    ``accum``); none of its arrays enters the program.  So one
    executable serves every plan of its shape, its size does not grow
    with L, and XLA folds nothing derived from the plan at compile
    time.  Only ``vals`` (argument 2) is donated: ``perm``/``slot``
    belong to the cached plan and serve every later request.
    """
    def scatter(perm, slot, vals):  # the trace's module: ``jit_scatter``
        return dataclasses.replace(pat, perm=perm, slot=slot).scatter(vals)

    fn = jax.vmap(scatter, in_axes=(None, None, 0)) if batched else scatter
    return jax.jit(fn, donate_argnums=(2,) if donate else ())


# ---------------------------------------------------------------------------
# The service
# ---------------------------------------------------------------------------
class PlanService:
    """Thread-safe serving front end over the plan/fill core.

    One instance per serving process.  Symbolic plans are shared with
    (and served from) the global ``sparse2``/SpGEMM LRUs — so existing
    ``sparse2``/``ops.matmul`` callers and the service warm each other —
    while the AOT executable tier is per-service (executables bind to
    this process's devices).

    Parameters
    ----------
    cache_dir:
        Optional persistence root.  When set, plan/product entries are
        written through on first use, loaded back on construction
        (``loaded_plans``/``loaded_products`` report how many), and
        JAX's persistent compilation cache is turned on
        (:func:`enable_compilation_cache`: ``JAX_COMPILATION_CACHE_DIR``
        where set, else the checkout's fixed ``.jax_cache``).
    exec_capacity:
        Executable-tier LRU capacity (env override:
        ``REPRO_EXEC_CACHE_SIZE``).
    donate:
        Donate request value buffers to the fill executables.  Default:
        on for GPU/TPU backends, off on CPU (which cannot donate and
        would warn per compile).
    method:
        Default planning backend for requests (same contract as
        ``fsparse(..., method=)``); per-call ``method=`` overrides.
    """

    def __init__(self, *, cache_dir=None, exec_capacity: int = 64,
                 donate: bool | None = None, method: str | None = None):
        self.method = method
        self._requests = itertools.count()  # the ``request`` span stat
        self.donate = (
            jax.default_backend() in ("gpu", "tpu")
            if donate is None else bool(donate)
        )
        self._execs = LRUCache(exec_capacity, name="aot-exec",
                               env="REPRO_EXEC_CACHE_SIZE")
        self._persisted: set = set()
        self._persist_lock = threading.Lock()
        self.cache_dir = None
        self.loaded_plans = 0
        self.loaded_products = 0
        self.loaded_tuning_entries = 0
        if cache_dir is not None:
            self.cache_dir = Path(cache_dir)
            self.cache_dir.mkdir(parents=True, exist_ok=True)
            enable_compilation_cache()
            self.loaded_plans, self.loaded_products = load_caches(
                self.cache_dir
            )
            # measured tuning table persists alongside the plan caches:
            # a restarted server resumes with the same policies (and
            # therefore the same AOT executable keys) it tuned before.
            table_path = self.cache_dir / tuning.TABLE_FILENAME
            if table_path.is_file():
                self.loaded_tuning_entries = tuning.get_table().load(
                    table_path
                )

    # -- persistence -------------------------------------------------------
    def _persist(self, kind: str, key, value) -> None:
        if self.cache_dir is None:
            return
        digest = (kind, _entry_digest(key))
        with self._persist_lock:
            if digest in self._persisted:
                return
            self._persisted.add(digest)
        try:
            _write_entry(self.cache_dir, kind, key, value)
        except Exception as e:  # noqa: BLE001 - serving must not crash
            warnings.warn(
                f"could not persist {kind} cache entry: "
                f"{type(e).__name__}: {e}",
                CacheCorruptionWarning,
                stacklevel=2,
            )

    def save(self) -> int:
        """Flush every in-memory plan/product entry to ``cache_dir``
        (plus the tuning table when it holds measured entries)."""
        if self.cache_dir is None:
            raise ValueError("PlanService has no cache_dir to save into")
        table = tuning.get_table()
        if len(table):
            table.save(self.cache_dir / tuning.TABLE_FILENAME)
        return save_caches(self.cache_dir)

    def _retire_persisted(self, old_key, old_structure_key) -> None:
        """Drop on-disk entries for a structure rewritten by an update.

        The plan entry is addressed directly by its key; product entries
        are keyed on *both* operands' structure keys, so the on-disk
        product files are scanned and any whose key references the
        retired structure is unlinked.  All best-effort: a stale file
        that survives only costs one wasted load on the next restart
        (the in-memory caches were already purged).
        """
        if self.cache_dir is None:
            return
        with self._persist_lock:
            self._persisted.discard(("plan", _entry_digest(old_key)))
        try:
            _entry_path(self.cache_dir, "plan", old_key).unlink(
                missing_ok=True)
        except OSError:
            pass
        for path in self.cache_dir.glob("product-*.pkl"):
            try:
                with open(path, "rb") as f:
                    payload = pickle.load(f)
                k = payload.get("key", ())
                if len(k) >= 2 and old_structure_key in (k[0], k[1]):
                    with self._persist_lock:
                        self._persisted.discard(
                            ("product", _entry_digest(payload["key"])))
                    path.unlink(missing_ok=True)
            except Exception:  # noqa: BLE001 - stale file, not a crash
                pass

    # -- AOT executable tier ----------------------------------------------
    def _aot(self, ekey, build):
        # the tuning fingerprint is folded into every executable key:
        # a re-tune (new measured table) retires stale executables
        # lowered under the old policy instead of replaying them.
        hit = 1

        def compile_():
            nonlocal hit
            hit = 0
            with span(COMPILE):
                return build()

        with span(EXEC_CACHE) as s:
            exe = self._execs.get_or_create(
                ekey + (tuning.tuning_fingerprint(),), compile_
            )
            s.set_metadata(hit=hit)
            return exe

    def _fill_executable(self, pat: SparsePattern, vals_shape, vals_dtype,
                         batch: int | None = None):
        """Compiled numeric fill for one shape (optionally vmap-batched),
        called as ``fill(pat.perm, pat.slot, vals)``.

        Keyed by ``(L, nzmax, accum, dtype, batch)``, not by structure:
        every plan of that shape shares the executable (see
        :func:`_served_fill`).
        """
        dtype = jnp.dtype(vals_dtype)
        batch = None if batch is None else int(batch)
        ekey = ("fill", int(vals_shape[0]), pat.nzmax, pat.accum, dtype.str,
                batch)

        def build():
            shape = tuple(vals_shape) if batch is None \
                else (batch,) + tuple(vals_shape)
            fill = _served_fill(pat, batched=batch is not None,
                                donate=self.donate)
            return fill.lower(
                jax.ShapeDtypeStruct(pat.perm.shape, pat.perm.dtype),
                jax.ShapeDtypeStruct(pat.slot.shape, pat.slot.dtype),
                jax.ShapeDtypeStruct(shape, dtype),
            ).compile()

        return self._aot(ekey, build)

    # -- request API -------------------------------------------------------
    def assemble(self, ii, jj, ss, shape=None, nzmax: int | None = None,
                 *, method: str | None = None, accum: str = "sum") -> CSC:
        """Matlab-style assembly served from the plan + executable caches.

        Same contract and bit-identical results as
        :func:`repro.sparse.fsparse`; a hot structure pays only one
        compiled O(L) fill executable call.
        """
        with span(ASSEMBLE, request=next(self._requests)) as request:
            key, pat, vals = _lookup_values(
                ii, jj, ss, shape, nzmax,
                method=self.method if method is None else method,
                accum=accum,
            )
            request.set_metadata(L=vals.shape[0])
            if not isinstance(pat, SparsePattern):
                # sharded plans run their own distributed fill (no AOT
                # tier: executables would pin one mesh layout per entry)
                with span(FILL):
                    return pat.assemble(vals)
            maybe_validate_pattern(pat, subject="PlanService.assemble")
            self._persist("plan", key, pat)
            fill = self._fill_executable(pat, vals.shape, vals.dtype)
            with span(FILL):
                data = fill(pat.perm, pat.slot, vals)
            return self._wrap(pat, data)

    def assemble_many(self, requests, *, method: str | None = None,
                      accum: str = "sum") -> list:
        """Batched front end: one fill executable per structure group.

        ``requests`` is an iterable of ``(ii, jj, ss)`` or
        ``(ii, jj, ss, shape)`` tuples from independent streams.  The
        requests are grouped by structure identity; each group of size
        B > 1 is served by a single ``vmap``-batched AOT fill over the
        stacked value vectors (the ``assemble_batch`` ride), and the
        results come back in request order, bit-identical to per-request
        :meth:`assemble`.
        """
        with span(ASSEMBLE_MANY, request=next(self._requests)):
            looked = []
            for req in requests:
                ii, jj, ss = req[0], req[1], req[2]
                shape = req[3] if len(req) > 3 else None
                looked.append(_lookup_values(
                    ii, jj, ss, shape,
                    method=self.method if method is None else method,
                    accum=accum,
                ))
            groups: dict = {}
            for idx, (key, _, vals) in enumerate(looked):
                groups.setdefault((key, vals.dtype.str), []).append(idx)
            results: list = [None] * len(looked)
            for (key, _), idxs in groups.items():
                pat = looked[idxs[0]][1]
                if not isinstance(pat, SparsePattern):
                    for i in idxs:
                        results[i] = pat.assemble(looked[i][2])
                    continue
                self._persist("plan", key, pat)
                vals0 = looked[idxs[0]][2]
                if len(idxs) == 1:
                    fill = self._fill_executable(pat, vals0.shape,
                                                 vals0.dtype)
                    results[idxs[0]] = self._wrap(
                        pat, fill(pat.perm, pat.slot, vals0))
                    continue
                fill = self._fill_executable(pat, vals0.shape, vals0.dtype,
                                             batch=len(idxs))
                stacked = jnp.stack([looked[i][2] for i in idxs])
                data_b = fill(pat.perm, pat.slot, stacked)
                for b, i in enumerate(idxs):
                    results[i] = self._wrap(pat, data_b[b])
            return results

    def update_structure(self, ii, jj, ss, add_ii, add_jj, add_ss,
                         shape=None, nzmax: int | None = None, *,
                         drop_mask=None, method: str | None = None,
                         accum: str = "sum",
                         nzmax_slack: int = 0) -> CSC:
        """Absorb a structural delta without cold-starting the structure.

        Runs :func:`repro.sparse.plan_update` (merge-forward delta
        re-planning through the shared plan LRU), then reconciles the
        serving tiers: SpGEMM/SpMV executables lowered against the *old*
        structure's index arrays are retired from the executable LRU,
        and persisted entries for the old structure are unlinked from
        ``cache_dir``.  Fill executables belong to a shape, not a
        structure, so none is retired, and the updated structure's fill
        compiles only if no structure of its shape has been served yet.
        Executables for unrelated structures are untouched, so a warm
        service absorbs a delta at the cost of one merge, not a cache
        flush.

        Returns the assembled updated matrix (bit-identical to a cold
        :meth:`assemble` over the concatenated surviving + delta
        triplets).
        """
        with span(UPDATE_STRUCTURE, request=next(self._requests)):
            res = plan_update(
                ii, jj, ss, add_ii, add_jj, add_ss, shape, nzmax,
                drop_mask=drop_mask,
                method=self.method if method is None else method,
                accum=accum, nzmax_slack=nzmax_slack,
            )
            if res.pattern is not res.old_pattern:
                from .spgemm import _structure_key

                old_sk = _structure_key(res.old_pattern)

                def _stale(ekey) -> bool:
                    kind = ekey[0]
                    if kind == "multiply":
                        return old_sk in (ekey[1][0], ekey[1][1])
                    if kind == "spmv":
                        return ekey[2] == old_sk
                    return False

                self._execs.purge(_stale)
                self._retire_persisted(res.old_key, old_sk)
            maybe_validate_pattern(res.pattern,
                                   subject="PlanService.update_structure")
            self._persist("plan", res.key, res.pattern)
            pat = res.pattern
            fill = self._fill_executable(pat, res.coo.vals.shape,
                                         res.coo.vals.dtype)
            return self._wrap(pat, fill(pat.perm, pat.slot, res.coo.vals))

    def multiply(self, A, B, *, method: str | None = None,
                 nzmax: int | None = None,
                 flops_max: int | None = None) -> CSC:
        """Sparse x sparse product through cached plan + AOT executable.

        Same results as ``ops.matmul(A, B)``; the symbolic product plan
        comes from the shared SpGEMM LRU (and is persisted), the
        O(flops) numeric refill from a compiled executable.
        """
        with span(MULTIPLY, request=next(self._requests)):
            Ac = convert(A, "csc")
            Bc = convert(B, "csc")
            key, pp = product_lookup(Ac, Bc, method=method, nzmax=nzmax,
                                     flops_max=flops_max)
            maybe_validate_pattern(pp, subject="PlanService.multiply")
            self._persist("product", key, pp)
            ekey = ("multiply", key, Ac.data.dtype.str, Bc.data.dtype.str)

            def build():
                jitted = jax.jit(pp.multiply)
                return jitted.lower(
                    jax.ShapeDtypeStruct(Ac.data.shape, Ac.data.dtype),
                    jax.ShapeDtypeStruct(Bc.data.shape, Bc.data.dtype),
                ).compile()

            return self._aot(ekey, build)(Ac.data, Bc.data)

    def spmv(self, S, x):
        """``S @ x`` (dense vector/matrix) via a per-structure executable.

        The per-format dispatch (:func:`repro.sparse.ops.spmv_impl`) is
        resolved once at lowering time; formats without a flat
        column/row-compressed structure (e.g. sharded block formats)
        fall back to the ordinary ``ops.matmul`` dispatch.
        """
        with span(SPMV, request=next(self._requests)):
            x = jnp.asarray(x)
            if x.ndim not in (1, 2):
                raise ValueError(
                    f"spmv expects a vector or matrix, got ndim={x.ndim}"
                )
            fn, Sr = spmv_impl(S)
            fields = _SPMV_NUMERIC_FIELDS.get(type(Sr).__name__)
            if fields is None or not hasattr(Sr, "indices"):
                return _ops_matmul(Sr, x)
            from .spgemm import _structure_key

            nums = tuple(getattr(Sr, f) for f in fields)
            ekey = ("spmv", type(Sr).__name__, _structure_key(Sr),
                    tuple(n.dtype.str for n in nums),
                    getattr(Sr, "block", None), tuple(x.shape), x.dtype.str)

            def build():
                @jax.named_scope("spmv")
                def f(*args):
                    *vals, xv = args
                    A = dataclasses.replace(Sr, **dict(zip(fields, vals)))
                    if xv.ndim == 1:
                        return fn(A, xv)
                    return jax.vmap(lambda col: fn(A, col),
                                    in_axes=1, out_axes=1)(xv)

                return jax.jit(f).lower(
                    *(jax.ShapeDtypeStruct(n.shape, n.dtype) for n in nums),
                    jax.ShapeDtypeStruct(x.shape, x.dtype),
                ).compile()

            return self._aot(ekey, build)(*nums, x)

    # -- introspection -----------------------------------------------------
    @staticmethod
    def _wrap(pat: SparsePattern, data) -> CSC:
        return CSC(data=data, indices=pat.indices, indptr=pat.indptr,
                   nnz=pat.nnz, shape=pat.shape)

    def stats(self) -> dict:
        """All cache tiers' metrics in one dict (the ops dashboard)."""
        return {
            "plan": plan_cache_info(),
            "alias": alias_cache_info(),
            "product": product_cache_info(),
            "exec": self._execs.info(),
            "loaded_plans": self.loaded_plans,
            "loaded_products": self.loaded_products,
            "loaded_tuning_entries": self.loaded_tuning_entries,
            "tuning_fingerprint": tuning.tuning_fingerprint(),
            "persisted": len(self._persisted),
            "cache_dir": None if self.cache_dir is None
            else str(self.cache_dir),
            "donate": self.donate,
        }
