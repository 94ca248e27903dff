"""Shared helpers for the Pallas TPU kernels.

All kernels are written against TPU tiling constraints (last dim a
multiple of 128 lanes, 8 sublanes) and validated on CPU with
``interpret=True``; :func:`resolve_interpret` picks the mode when a
kernel is traced, so importing a kernel module touches no backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret=None`` means interpret mode unless the default
    backend is a TPU; an explicit flag is returned unchanged."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


LANES = 128
SUBLANES = 8


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def pad_to(x: jax.Array, size: int, fill) -> jax.Array:
    """Pad the last axis of ``x`` up to ``size`` with ``fill``."""
    L = x.shape[-1]
    if L == size:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, size - L)]
    return jnp.pad(x, pad, constant_values=fill)
