"""Unified execution-policy layer: the tunables registry + autotune
cache.

Every kernel family used to freeze its execution policy into code —
``default_method()`` hardcoded backend picks in ``dispatch.py``, the
8 MB VMEM residency cap existed twice (``FUSED_RESIDENT_MAX_BYTES`` and
a copy as ``MERGE_RESIDENT_MAX_BYTES``), ``_perm_radix`` pinned
``block_b=4096``, and the radix digit planner ran on hand-set cost
constants.  This module single-homes all of it:

* :class:`KernelSpec` / :class:`Knob` — each kernel family registers a
  declarative spec naming its knobs (sort method, merge method, digit
  width, tile sizes, residency budget) with the previous compile-time
  constants as *priors*.  :data:`RESIDENT_BUDGET_BYTES` is the single
  registry-owned VMEM budget every family's ``resident_max_bytes``
  prior points at.
* :class:`TuningTable` — resolves a policy per ``(backend, family,
  M, N, L, dtype)``: the spec's priors overlaid with any *measured*
  entries recorded by the autotuner, most-specific match last.  Tables
  persist as JSON next to the plan caches (``PlanService`` saves and
  restores ``tuning-table.json`` under its ``cache_dir``); corrupt
  files degrade to priors with a
  :class:`~repro.sparse.errors.CacheCorruptionWarning`.
* The autotuner CLI (``python -m repro.sparse.tuning``) benchmarks
  candidate configs per family and measures-and-overwrites the static
  priors; ``--prior-only`` resolves the table without measuring and
  asserts it consumes every ``vmem_report()`` row (the CI artifact).

Consumers never read constants again: ``dispatch.resolve_method`` /
``resolve_merge_method`` consult the table, every kernel-family
``ops.py`` resolves tile sizes and residency budgets through
:func:`resolve_policy` at trace time, and ``serving.PlanService`` folds
:func:`tuning_fingerprint` into its AOT executable keys so a re-tune
retires stale executables.

Environment knobs: ``REPRO_TUNE=0`` disables measured overrides
(priors only, end to end); ``REPRO_TUNING_CACHE_DIR`` names a directory
whose ``tuning-table.json`` is loaded into the process-global table on
first use.

    >>> resolve_policy("segment_sum", backend="cpu", measured=False)[
    ...     "resident_max_bytes"] == RESIDENT_BUDGET_BYTES
    True
    >>> resolve_policy("plan", backend="tpu", measured=False)["method"]
    'radix'
    >>> resolve_policy("plan", backend="cpu", measured=False)["method"]
    'fused'
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import threading
import warnings
from pathlib import Path

import numpy as np

from ..errors import CacheCorruptionWarning

__all__ = [
    "Knob",
    "KernelSpec",
    "RESIDENT_BUDGET_BYTES",
    "TABLE_FILENAME",
    "TuningTable",
    "default_cache_path",
    "get_table",
    "kernel_spec",
    "prior_policy",
    "prior_value",
    "register_kernel_spec",
    "registered_families",
    "reset_table",
    "resolve_policy",
    "set_table",
    "tuning_enabled",
    "tuning_fingerprint",
]

#: the single registry-owned VMEM residency budget: 8 MB of resident
#: operand buffers, leaving room for the index and output blocks on a
#: 16 MB core.  Every family's ``resident_max_bytes`` prior points
#: here; the deprecated ``FUSED_RESIDENT_MAX_BYTES`` /
#: ``MERGE_RESIDENT_MAX_BYTES`` names are aliases of this value.
RESIDENT_BUDGET_BYTES = 8 << 20

#: filename of a persisted table inside a cache directory (the same
#: directory ``PlanService(cache_dir=...)`` keeps its plan pickles in).
TABLE_FILENAME = "tuning-table.json"

#: on-disk schema version; bumped on incompatible layout changes so a
#: stale file degrades to priors instead of mis-resolving.
_SCHEMA = 1


def _default_backend() -> str:
    import jax

    return jax.default_backend()


def _dtype_name(dtype) -> str | None:
    if dtype is None:
        return None
    try:
        return np.dtype(dtype).name
    except TypeError:
        # extension dtypes (e.g. bfloat16 before ml_dtypes registers)
        return str(dtype)


def _bucket(v) -> int | None:
    """Power-of-two size bucket (``bit_length``); ``None`` is wildcard."""
    if v is None:
        return None
    return max(int(v), 1).bit_length()


# ---------------------------------------------------------------------------
# Declarative tunables registry
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Knob:
    """One tunable of a kernel family.

    ``default`` is the prior — either a plain value or a backend-keyed
    dict (``{"tpu": "radix", "*": "fused"}``); ``candidates`` is the
    value grid the autotuner sweeps (empty: not swept, only
    calibrated/overridden directly).
    """

    name: str
    default: object
    candidates: tuple = ()

    def prior(self, backend: str | None = None):
        if isinstance(self.default, dict):
            if backend in self.default:
                return self.default[backend]
            return self.default["*"]
        return self.default


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """A kernel family's declared knob set (with priors)."""

    family: str
    knobs: tuple
    description: str = ""

    def knob_names(self) -> tuple:
        return tuple(k.name for k in self.knobs)

    def knob(self, name: str) -> Knob:
        for k in self.knobs:
            if k.name == name:
                return k
        raise KeyError(
            f"kernel family {self.family!r} has no knob {name!r}; "
            f"declared: {self.knob_names()}"
        )

    def priors(self, backend: str | None = None) -> dict:
        return {k.name: k.prior(backend) for k in self.knobs}


_SPECS: dict = {}
_SPECS_LOCK = threading.Lock()


def register_kernel_spec(spec: KernelSpec) -> None:
    """Register (or replace) a kernel family's tunables spec."""
    with _SPECS_LOCK:
        _SPECS[spec.family] = spec


def kernel_spec(family: str) -> KernelSpec:
    try:
        return _SPECS[family]
    except KeyError:
        raise KeyError(
            f"unknown kernel family {family!r}; "
            f"registered: {registered_families()}"
        ) from None


def registered_families() -> tuple:
    return tuple(sorted(_SPECS))


def prior_policy(family: str, backend: str | None = None) -> dict:
    """The spec's priors alone — what resolution falls back to."""
    return kernel_spec(family).priors(backend)


def prior_value(family: str, knob: str, backend: str | None = None):
    return kernel_spec(family).knob(knob).prior(backend)


# ---------------------------------------------------------------------------
# The measured table
# ---------------------------------------------------------------------------
_ENTRY_AXES = ("backend", "M_bucket", "N_bucket", "L_bucket", "dtype")


@dataclasses.dataclass
class _Entry:
    family: str
    policy: dict
    backend: str | None = None
    M_bucket: int | None = None
    N_bucket: int | None = None
    L_bucket: int | None = None
    dtype: str | None = None
    source: str = "measured"

    def key(self) -> tuple:
        return (self.family,) + tuple(
            getattr(self, a) for a in _ENTRY_AXES
        )

    def specificity(self) -> int:
        return sum(getattr(self, a) is not None for a in _ENTRY_AXES)

    def matches(self, family, backend, mb, nb, lb, dtype) -> bool:
        if self.family != family:
            return False
        for mine, theirs in (
            (self.backend, backend),
            (self.M_bucket, mb),
            (self.N_bucket, nb),
            (self.L_bucket, lb),
            (self.dtype, dtype),
        ):
            if mine is not None and mine != theirs:
                return False
        return True

    def as_dict(self) -> dict:
        d = {"family": self.family, "policy": dict(self.policy),
             "source": self.source}
        for a in _ENTRY_AXES:
            if getattr(self, a) is not None:
                d[a] = getattr(self, a)
        return d


class TuningTable:
    """Measured policy overrides over the registry priors.

    Resolution: start from :meth:`KernelSpec.priors` for the backend,
    then overlay every matching measured entry least-specific first —
    a ``(backend, L-bucket)`` entry beats a backend-wide one.  With
    ``measured=False`` (or ``REPRO_TUNE=0`` in the environment) the
    priors are returned untouched.
    """

    def __init__(self):
        self._lock = threading.RLock()
        self._entries: list = []

    # -- recording ---------------------------------------------------------
    def record(
        self,
        family: str,
        policy: dict,
        *,
        backend: str | None = None,
        M=None,
        N=None,
        L=None,
        dtype=None,
        source: str = "measured",
    ) -> None:
        """Record measured knob overrides for one (family, shape) cell.

        ``policy`` holds only the overridden knobs; unknown families or
        knobs raise ``KeyError`` (the registry is the schema).  A new
        record for the same cell replaces the old one.
        """
        spec = kernel_spec(family)
        for name in policy:
            spec.knob(name)  # KeyError on unknown knob
        entry = _Entry(
            family=family,
            policy=dict(policy),
            backend=backend,
            M_bucket=_bucket(M),
            N_bucket=_bucket(N),
            L_bucket=_bucket(L),
            dtype=_dtype_name(dtype),
            source=source,
        )
        with self._lock:
            self._entries = [
                e for e in self._entries if e.key() != entry.key()
            ]
            self._entries.append(entry)

    def clear(self) -> None:
        with self._lock:
            self._entries = []

    def entries(self) -> list:
        with self._lock:
            return [e.as_dict() for e in self._entries]

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    # -- resolution --------------------------------------------------------
    def resolve(
        self,
        family: str,
        *,
        backend: str | None = None,
        M=None,
        N=None,
        L=None,
        dtype=None,
        measured: bool = True,
    ) -> dict:
        """The effective policy for one kernel invocation."""
        if backend is None:
            backend = _default_backend()
        policy = kernel_spec(family).priors(backend)
        if not (measured and tuning_enabled()):
            return policy
        mb, nb, lb = _bucket(M), _bucket(N), _bucket(L)
        dt = _dtype_name(dtype)
        with self._lock:
            hits = [
                e
                for e in self._entries
                if e.matches(family, backend, mb, nb, lb, dt)
            ]
        for e in sorted(hits, key=_Entry.specificity):
            policy.update(e.policy)
        return policy

    # -- persistence -------------------------------------------------------
    def fingerprint(self) -> str:
        """Content hash of the measured state (stable across processes).

        An empty table fingerprints as ``"prior"`` — the AOT executable
        keys built before any tune stay valid until a measured entry
        lands.
        """
        with self._lock:
            if not self._entries:
                return "prior"
            blob = json.dumps(
                sorted(self.entries(), key=json.dumps), sort_keys=True
            )
        return hashlib.sha256(blob.encode()).hexdigest()[:16]

    def save(self, path) -> Path:
        """Atomically persist the table as JSON (``tmp`` + rename)."""
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        payload = {
            "schema": _SCHEMA,
            "fingerprint": self.fingerprint(),
            "entries": self.entries(),
        }
        tmp = path.with_suffix(f".tmp.{os.getpid()}")
        with open(tmp, "w") as fh:
            json.dump(payload, fh, indent=1, sort_keys=True)
            fh.write("\n")
        os.replace(tmp, path)
        return path

    def load(self, path) -> int:
        """Merge entries from a persisted table; returns how many.

        A corrupt file or a stale schema degrades to the priors with a
        :class:`CacheCorruptionWarning` (same contract as the plan
        pickles); individually invalid entries (unknown family/knob)
        are skipped entry-by-entry with the same warning.
        """
        path = Path(path)
        try:
            with open(path) as fh:
                payload = json.load(fh)
            if payload.get("schema") != _SCHEMA:
                raise ValueError(
                    f"schema {payload.get('schema')!r} != {_SCHEMA}"
                )
            raw = payload["entries"]
            if not isinstance(raw, list):
                raise TypeError("entries is not a list")
        except Exception as e:  # noqa: BLE001 - degrade to priors
            warnings.warn(
                f"ignoring corrupt tuning table {path}: "
                f"{type(e).__name__}: {e} — resolving from priors",
                CacheCorruptionWarning,
                stacklevel=2,
            )
            return 0
        loaded = 0
        for rec in raw:
            try:
                self.record(
                    rec["family"],
                    rec["policy"],
                    backend=rec.get("backend"),
                    source=rec.get("source", "measured"),
                )
                # buckets were persisted pre-bucketed: restore verbatim
                with self._lock:
                    e = self._entries[-1]
                    e.M_bucket = rec.get("M_bucket")
                    e.N_bucket = rec.get("N_bucket")
                    e.L_bucket = rec.get("L_bucket")
                    e.dtype = rec.get("dtype")
                loaded += 1
            except Exception as e:  # noqa: BLE001 - skip bad entry
                warnings.warn(
                    f"skipping invalid tuning entry {rec!r} from "
                    f"{path}: {type(e).__name__}: {e}",
                    CacheCorruptionWarning,
                    stacklevel=2,
                )
        return loaded


# ---------------------------------------------------------------------------
# Process-global table + environment knobs
# ---------------------------------------------------------------------------
_TABLE = None
_TABLE_LOCK = threading.Lock()


def tuning_enabled() -> bool:
    """``False`` when ``REPRO_TUNE`` is ``0``/``false``/``off``."""
    return os.environ.get("REPRO_TUNE", "1").strip().lower() not in (
        "0",
        "false",
        "off",
    )


def default_cache_path() -> Path | None:
    """``$REPRO_TUNING_CACHE_DIR/tuning-table.json`` when the env var
    is set, else ``None``."""
    d = os.environ.get("REPRO_TUNING_CACHE_DIR")
    if not d:
        return None
    return Path(d) / TABLE_FILENAME


def get_table() -> TuningTable:
    """The process-global table (lazily loaded from the env cache dir)."""
    global _TABLE
    with _TABLE_LOCK:
        if _TABLE is None:
            table = TuningTable()
            path = default_cache_path()
            if path is not None and path.exists():
                table.load(path)
            _TABLE = table
        return _TABLE


def set_table(table: TuningTable) -> None:
    global _TABLE
    with _TABLE_LOCK:
        _TABLE = table


def reset_table() -> None:
    """Drop the global table (re-resolved lazily; test/re-tune hook)."""
    global _TABLE
    with _TABLE_LOCK:
        _TABLE = None


def resolve_policy(
    family: str,
    *,
    backend: str | None = None,
    M=None,
    N=None,
    L=None,
    dtype=None,
    measured: bool = True,
) -> dict:
    """Resolve one kernel invocation's policy via the global table."""
    return get_table().resolve(
        family,
        backend=backend,
        M=M,
        N=N,
        L=L,
        dtype=dtype,
        measured=measured,
    )


def tuning_fingerprint() -> str:
    """The global table's content hash (``"prior"`` until a tune)."""
    return get_table().fingerprint()


# ---------------------------------------------------------------------------
# Built-in family specs (priors == the former compile-time constants)
# ---------------------------------------------------------------------------
register_kernel_spec(
    KernelSpec(
        "plan",
        (
            Knob(
                "method",
                {"tpu": "radix", "*": "fused"},
                candidates=("jnp", "fused", "pallas", "radix"),
            ),
        ),
        description="symbolic-phase sort backend "
        "(dispatch.sorted_permutation)",
    )
)
register_kernel_spec(
    KernelSpec(
        "merge",
        (
            # "jnp" everywhere: the Pallas search gathers from 1-D
            # VMEM-resident keys, which the TPU compiler does not lower
            Knob("method", "jnp", candidates=("jnp", "pallas")),
            Knob("block_b", 65536, candidates=(32768, 65536, 131072)),
            Knob("resident_max_bytes", RESIDENT_BUDGET_BYTES),
        ),
        description="delta merge-by-key search "
        "(SparsePattern.update)",
    )
)
register_kernel_spec(
    KernelSpec(
        "radix_sort",
        (
            Knob("block_b", 4096, candidates=(4096, 8192, 16384, 32768)),
            Knob("block_t", 512, candidates=(256, 512, 1024)),
            Knob("max_bits", 11, candidates=(8, 9, 10, 11)),
            Knob("pass_cost", 192),
            Knob("tile_cost", 3),
            Knob("launch_cost", 50_000),
        ),
        description="LSD radix partition planner "
        "(digit-pass cost model + tiles)",
    )
)
register_kernel_spec(
    KernelSpec(
        "segment_sum",
        (
            Knob("block_b", 65536, candidates=(32768, 65536, 131072)),
            Knob("scan_block_b", 4096, candidates=(4096, 8192, 16384)),
            Knob("resident_max_bytes", RESIDENT_BUDGET_BYTES),
        ),
        description="fused gather + masked segment reductions "
        "(numeric fills / SpGEMM)",
    )
)
register_kernel_spec(
    KernelSpec(
        "spmv",
        (Knob("block_r", 256, candidates=(128, 256, 512)),),
        description="padded-ELL SpMV row tile",
    )
)
register_kernel_spec(
    KernelSpec(
        "spmv_sym",
        (
            # "ref" everywhere: the Pallas kernels gather from a 1-D
            # VMEM-resident vector, which the TPU compiler does not lower
            Knob("method", "ref", candidates=("ref", "pallas")),
            Knob("block_b", 65536, candidates=(32768, 65536, 131072)),
            Knob("block_t", 4096, candidates=(2048, 4096, 8192)),
            Knob("resident_max_bytes", RESIDENT_BUDGET_BYTES),
        ),
        description="symmetric / blocked SpMV streams "
        "(x VMEM-resident)",
    )
)
register_kernel_spec(
    KernelSpec(
        "counting_sort",
        (
            Knob("block_b", 1024, candidates=(1024, 2048, 4096)),
            Knob("block_t", 512, candidates=(256, 512, 1024)),
        ),
        description="per-dimension counting sort "
        "(method='pallas' planner)",
    )
)
