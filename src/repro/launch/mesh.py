"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so that
importing this module never touches jax device state.  The dry-run
(``launch/dryrun.py``) asks for 512 placeholder host devices in its
``main()``, before any backend starts.

Axes:
  single-pod : (16, 16)      -> ("data", "model")       = 256 chips
  multi-pod  : (2, 16, 16)   -> ("pod", "data", "model") = 512 chips

Batch parallelism uses ("pod", "data") jointly; tensor/expert
parallelism uses "model"; the cross-pod gradient reduce rides the
"pod" axis (hierarchical: in-pod reduce-scatter first — the paper's
two-level counter accumulation, at datacenter scale).
"""
from __future__ import annotations

import functools

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


@functools.lru_cache(maxsize=None)
def make_data_mesh(n: int | None = None, *, axis: str = "data"):
    """One-axis mesh over (up to) all present devices.

    The default mesh of the sharded assembly path
    (``repro.sparse.sharded`` / ``method="sharded"``): sparse assembly
    only redistributes over one axis, so tensor-parallel structure is
    irrelevant here.  Memoized — the device set is fixed per process,
    and hot callers (the ``sparse2`` plan-cache fast path) resolve the
    default mesh on every call.
    """
    n = len(jax.devices()) if n is None else n
    return jax.make_mesh((n,), (axis,), axis_types=(AxisType.Auto,))


def make_host_mesh(*, data: int | None = None, model: int = 1):
    """Small mesh over the actually-present devices (tests/examples)."""
    n = len(jax.devices())
    if data is None:
        data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def batch_axes(mesh) -> tuple[str, ...]:
    """The mesh axes that carry data parallelism."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def tp_size(mesh) -> int:
    return mesh.shape["model"]


def dp_size(mesh) -> int:
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n
