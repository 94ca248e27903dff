"""Single backend-dispatch point for the assembly sort strategies.

Every planner/assembler selects its backend through one ``method=``
string (replacing the old ``fused=`` boolean threading):

  "jnp"    two stable counting sorts (row pass, then column pass) via
           XLA's stable sort — the paper's Parts 1-3 structure
  "fused"  one stable sort on the fused key ``col * (M+1) + row``
           (beyond-paper; widens the key to int64 when x64 mode is
           enabled, and falls back to "jnp" — with a one-time warning —
           only when the key overflows int32 *and* int64 is
           unavailable)
  "pallas" the Pallas counting-sort kernels (MXU placement) — one full
           histogram/placement pass per matrix dimension
  "radix"  the Pallas LSD radix-partition planner
           (``repro.kernels.radix_sort``): the (col, row) pair is kept
           as a two-word key and sorted a few bits at a time, so the
           per-pass bin count is a small constant for any M/N and no
           overflow fallback exists — the TPU production default

All backends produce the *identical* (col,row)-ordered permutation with
duplicates adjacent and padding (``row == M``) last within its column
group, so the shared Parts-3/4 tail (``pattern_from_perm``) and the
numeric phase are backend-agnostic.

New backends register with :func:`register_method`; consumers go
through :func:`sorted_permutation` and never branch on the name again.
``method=None`` anywhere resolves to :func:`default_method`, which is
backend-aware: ``"radix"`` on TPU, ``"fused"`` off-TPU (where the
Pallas kernels would run in interpret mode and the XLA sort wins).

The *merge* backends (``SparsePattern.update``'s delta merge-by-key —
``repro.kernels.merge``) follow the same pattern with their own
registry: :func:`register_merge_method` / :func:`merge_search` /
:func:`default_merge_method` (``"jnp"`` on every backend: the Pallas
search does not compile for the TPU).
"""
from __future__ import annotations

import warnings
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from . import tuning
from .errors import FallbackWarning

PermFn = Callable[..., jax.Array]

_METHODS: Dict[str, PermFn] = {}

#: the production (TPU) planning backend — what ``method=None``
#: resolves to on accelerator backends where the Pallas kernels compile
#: natively.  The value is owned by the ``plan`` tuning spec; these
#: names are kept as the documented prior pins.
DEFAULT_METHOD_TPU = tuning.prior_value("plan", "method", backend="tpu")
#: the off-TPU default: Pallas runs in interpret mode there, so the
#: fused-key XLA sort is the fastest correct choice (it widens to int64
#: under x64 and only warns+falls back to two passes in the
#: overflow-without-x64 corner).
DEFAULT_METHOD_INTERPRET = tuning.prior_value(
    "plan", "method", backend="cpu"
)


def register_method(name: str, fn: PermFn) -> None:
    """Register a sort backend: ``fn(rows, cols, *, M, N, **kw) -> perm``."""
    _METHODS[name] = fn


def available_methods() -> tuple[str, ...]:
    return tuple(sorted(_METHODS))


def default_method() -> str:
    """The backend used when callers pass ``method=None``.

    Resolved through the tuning table (family ``"plan"``): the priors
    are backend-aware — ``"radix"`` on TPU, ``"fused"`` where Pallas
    would interpret — and a measured tune can overwrite them per
    (backend, shape bucket).
    """
    return str(tuning.resolve_policy("plan")["method"])


def resolve_method(method: str | None) -> str:
    """Map ``None`` to the production default, pass names through."""
    return default_method() if method is None else method


def sorted_permutation(
    rows: jax.Array, cols: jax.Array, *, M: int, N: int,
    method: str | None = None, **kwargs
) -> jax.Array:
    """(col,row)-stable-ordered permutation via the selected backend."""
    method = resolve_method(method)
    try:
        fn = _METHODS[method]
    except KeyError:
        raise ValueError(
            f"unknown assembly method {method!r}; "
            f"available: {available_methods()}"
        ) from None
    return fn(rows, cols, M=M, N=N, **kwargs)


def method_from_fused(fused: bool | None, method: str | None) -> str:
    """Back-compat shim: map the deprecated ``fused=`` flag to a method.

    An explicit ``fused=True/False`` keeps its historical meaning
    ("fused"/"jnp"); with neither argument given the modern default
    backend applies.
    """
    if method is not None:
        return method
    if fused is None:
        return default_method()
    return "fused" if fused else "jnp"


# ---------------------------------------------------------------------------
# Built-in backends
# ---------------------------------------------------------------------------
def _perm_jnp(rows, cols, *, M: int, N: int) -> jax.Array:
    """Two-pass path: stable row sort, then stable column sort (paper)."""
    del N
    rank = jnp.argsort(rows, stable=True).astype(jnp.int32)
    rank2 = jnp.argsort(cols[rank], stable=True).astype(jnp.int32)
    del M
    return rank[rank2]


_FUSED_FALLBACK_WARNED = False


def _reset_fused_fallback_warning() -> None:
    """Test hook: re-arm the one-time int32-overflow fallback warning."""
    global _FUSED_FALLBACK_WARNED
    _FUSED_FALLBACK_WARNED = False


def _perm_fused(rows, cols, *, M: int, N: int) -> jax.Array:
    """Fused-key single sort; int64 key above the int32 range.

    Only when the key overflows int32 *and* x64 mode is off does this
    degrade to the two-pass path — with a one-time warning, because the
    caller asked for one pass and silently got two.  (``method="radix"``
    has no such regime at all.)
    """
    if (M + 1) * (N + 1) < 2**31:
        key = cols * jnp.int32(M + 1) + rows
    elif jax.dtypes.canonicalize_dtype(jnp.int64) == jnp.dtype(jnp.int64):
        key = cols.astype(jnp.int64) * jnp.int64(M + 1) + \
            rows.astype(jnp.int64)
    else:
        global _FUSED_FALLBACK_WARNED
        if not _FUSED_FALLBACK_WARNED:
            _FUSED_FALLBACK_WARNED = True
            warnings.warn(
                f"method='fused': key (M+1)*(N+1) = {(M + 1) * (N + 1)} "
                "overflows int32 and x64 mode is disabled — falling back "
                "to the two-pass 'jnp' sort. Enable jax_enable_x64 or use "
                "method='radix' (no overflow regime) to keep a bounded "
                "pass count.",
                FallbackWarning,
                stacklevel=2,
            )
        return _perm_jnp(rows, cols, M=M, N=N)
    return jnp.argsort(key, stable=True).astype(jnp.int32)


def _perm_pallas(rows, cols, *, M: int, N: int,
                 block_b: int | None = None,
                 interpret: bool | None = None
                 ) -> jax.Array:
    """Pallas counting-sort kernels (imported lazily: no hard kernel dep)."""
    from ..kernels.counting_sort.ops import counting_sort

    rank, _ = counting_sort(
        rows, nbins=M + 1, block_b=block_b, interpret=interpret
    )
    rank2, _ = counting_sort(
        cols[rank], nbins=N + 1, block_b=block_b, interpret=interpret
    )
    return rank[rank2]


def _perm_radix(rows, cols, *, M: int, N: int, block_b: int | None = None,
                max_bits: int | None = None,
                interpret: bool | None = None) -> jax.Array:
    """Pallas LSD radix-partition planner (lazy import, as above)."""
    from ..kernels.radix_sort.ops import radix_sort_pair

    return radix_sort_pair(
        rows, cols, M=M, N=N, block_b=block_b, max_bits=max_bits,
        interpret=interpret,
    )


register_method("jnp", _perm_jnp)
register_method("fused", _perm_fused)
register_method("pallas", _perm_pallas)
register_method("radix", _perm_radix)


# ---------------------------------------------------------------------------
# Merge backends (SparsePattern.update's sorted-stream merge-by-key)
# ---------------------------------------------------------------------------
_MERGE_METHODS: Dict[str, PermFn] = {}


def register_merge_method(name: str, fn: PermFn) -> None:
    """Register a merge-search backend:
    ``fn(q_rows, q_cols, t_rows, t_cols, *, side, **kw) -> offsets``."""
    _MERGE_METHODS[name] = fn


def available_merge_methods() -> tuple[str, ...]:
    return tuple(sorted(_MERGE_METHODS))


def default_merge_method() -> str:
    """Backend used when callers pass ``merge_method=None`` (resolved
    through the tuning table, family ``"merge"``)."""
    return str(tuning.resolve_policy("merge")["method"])


def resolve_merge_method(method: str | None) -> str:
    return default_merge_method() if method is None else method


def merge_search(
    q_rows: jax.Array, q_cols: jax.Array,
    t_rows: jax.Array, t_cols: jax.Array, *,
    side: str = "left", method: str | None = None, **kwargs
) -> jax.Array:
    """Per-query insertion offsets into a (col,row)-sorted target stream.

    ``side="left"`` counts targets strictly below each query key,
    ``side="right"`` counts targets at-or-below — the two halves of a
    stable merge's tie rule.  All backends are bit-identical.
    """
    method = resolve_merge_method(method)
    try:
        fn = _MERGE_METHODS[method]
    except KeyError:
        raise ValueError(
            f"unknown merge method {method!r}; "
            f"available: {available_merge_methods()}"
        ) from None
    return fn(q_rows, q_cols, t_rows, t_cols, side=side, **kwargs)


def _merge_jnp(q_rows, q_cols, t_rows, t_cols, *, side="left"):
    """Pure-jnp vectorized binary search (lazy import, like the sorts)."""
    from ..kernels.merge.ref import merge_search_ref

    return merge_search_ref(q_rows, q_cols, t_rows, t_cols, side=side)


def _merge_pallas(q_rows, q_cols, t_rows, t_cols, *, side="left",
                  block_b: int | None = None,
                  interpret: bool | None = None):
    """Residency-guarded Pallas search (falls back to jnp past budget)."""
    from ..kernels.merge.ops import merge_search as _pallas_search

    return _pallas_search(q_rows, q_cols, t_rows, t_cols, side=side,
                          block_b=block_b, interpret=interpret)


register_merge_method("jnp", _merge_jnp)
register_merge_method("pallas", _merge_pallas)
