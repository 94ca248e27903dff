"""Pallas two-way merge positioning kernel.

The merge half of ``SparsePattern.update``: each query key of one
sorted stream binary-searches its insertion offset into the *other*
(resident) sorted stream.  The target key arrays stay VMEM-resident
across grid steps — one input block spanning the whole grid, like the
value vector of ``segment_sum.gather_masked_cumsum`` — while the query
stream is blocked, so each grid step runs the full ``ceil(log2(n))``
search ladder with in-VMEM gathers and writes one int32 offset block.
No scratch carry is needed: query blocks are independent.

Bit-identical to ``ref.merge_search_ref`` (the dispatch fallback); the
residency budget that decides between them lives in ``ops.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import LANES, resolve_interpret, round_up
from .ref import _below, search_steps


def _merge_search_kernel(qr_ref, qc_ref, tr_ref, tc_ref, out_ref, *,
                         n_targets: int, steps: int, inclusive: bool):
    qr = qr_ref[...]
    qc = qc_ref[...]
    tr = tr_ref[...]
    tc = tc_ref[...]
    lo = jnp.zeros(qr.shape, jnp.int32)
    hi = jnp.full(qr.shape, n_targets, jnp.int32)
    for _ in range(steps):  # static unroll: log2(n_targets) ladder steps
        active = lo < hi
        mid = jnp.minimum((lo + hi) // 2, n_targets - 1)
        below = _below(tc[mid], tr[mid], qc, qr, inclusive=inclusive)
        lo = jnp.where(jnp.logical_and(active, below), mid + 1, lo)
        hi = jnp.where(jnp.logical_and(active, ~below), mid, hi)
    out_ref[...] = lo


@functools.partial(
    jax.jit, static_argnames=("side", "block_b", "interpret")
)
def merge_search_pallas(
    q_rows: jax.Array,
    q_cols: jax.Array,
    t_rows: jax.Array,
    t_cols: jax.Array,
    *,
    side: str = "left",
    block_b: int = 65536,
    interpret: bool | None = None,
) -> jax.Array:
    """Pallas counterpart of :func:`ref.merge_search_ref`.

    Targets must be (col, row)-sorted and small enough to stay resident
    (callers budget them against ``ops.MERGE_RESIDENT_MAX_BYTES``);
    padded target entries are never gathered — the search interval is
    bounded by the true ``n_targets`` and ``mid`` is clamped below it.
    """
    if side not in ("left", "right"):
        raise ValueError(f"side must be 'left' or 'right', got {side!r}")
    interpret = resolve_interpret(interpret)
    n = int(t_rows.shape[0])
    Lq = int(q_rows.shape[0])
    if n == 0 or Lq == 0:
        return jnp.zeros((Lq,), jnp.int32)
    block_b = min(block_b, round_up(max(Lq, 1), 4096))
    Lp = round_up(max(Lq, block_b), block_b)
    Tn = round_up(max(n, LANES), LANES)
    qr_p = jnp.pad(q_rows.astype(jnp.int32), (0, Lp - Lq))
    qc_p = jnp.pad(q_cols.astype(jnp.int32), (0, Lp - Lq))
    tr_p = jnp.pad(t_rows.astype(jnp.int32), (0, Tn - n))
    tc_p = jnp.pad(t_cols.astype(jnp.int32), (0, Tn - n))
    out = pl.pallas_call(
        functools.partial(
            _merge_search_kernel,
            n_targets=n,
            steps=search_steps(n),
            inclusive=(side == "right"),
        ),
        grid=(Lp // block_b,),
        in_specs=[
            pl.BlockSpec((block_b,), lambda b: (b,)),
            pl.BlockSpec((block_b,), lambda b: (b,)),
            pl.BlockSpec((Tn,), lambda b: (0,)),
            pl.BlockSpec((Tn,), lambda b: (0,)),
        ],
        out_specs=pl.BlockSpec((block_b,), lambda b: (b,)),
        out_shape=jax.ShapeDtypeStruct((Lp,), jnp.int32),
        interpret=interpret,
    )(qr_p, qc_p, tr_p, tc_p)
    return out[:Lq]
