"""Pallas kernels: fused both-triangles symmetric SpMV + BSR tiles.

The symmetric kernel streams the *halved* strict-upper slot stream once
— per block it gathers ``x`` in both directions from a VMEM-resident
vector and emits (a) the row-direction contributions for a collision
epilogue scatter and (b) the carry-extended running sum of the
column-direction contributions, from which the wrapper extracts each
column's total as an ``indptr`` boundary difference (the same
invertible-monoid trick as ``kernels/segment_sum``).  One pass over the
half stream covers both triangles — the ~2x bytes-moved reduction the
format exists for.

The BSR kernel tiles the stored block stream; ``x`` stays resident
reshaped ``(Nb, b)`` and each ``b x b`` tile contracts against its
aligned slice in registers (VPU elementwise + lane reduce — tiles are
far below the 128x128 MXU sweet spot).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import LANES, resolve_interpret, round_up


def _sym_streams_kernel(rows_ref, cols_ref, data_ref, x_ref,
                        up_ref, cs_ref, carry_ref, *, M: int):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    x = x_ref[...]
    rows = rows_ref[...]
    valid = rows < M
    r = jnp.where(valid, rows, 0)
    d = data_ref[...]
    zero = jnp.zeros((), d.dtype)
    up_ref[...] = jnp.where(valid, d * x[cols_ref[...]], zero)
    lo = jnp.where(valid, d * x[r], zero)
    c = jnp.cumsum(lo)
    cs_ref[...] = c + carry_ref[0]
    carry_ref[0] = carry_ref[0] + c[-1]


@functools.partial(jax.jit, static_argnames=("M", "block_b", "interpret"))
def sym_streams(rows, cols, data, x, *, M: int, block_b: int = 65536,
                interpret: bool | None = None):
    """Both per-entry contribution streams of the fused symmetric SpMV.

    Returns ``(up, cs)``: ``up[s] = a_s * x[col_s]`` (row-direction,
    caller scatter-adds by row) and ``cs`` the running global cumsum of
    ``a_s * x[row_s]`` (column-direction, caller differences at
    ``indptr`` boundaries).  ``rows`` carries ``M`` sentinels for
    padding; ``cols`` must be pre-clipped to ``[0, M)``.
    """
    interpret = resolve_interpret(interpret)
    L = rows.shape[0]
    block_b = min(block_b, round_up(max(L, 1), 4096))
    Lp = round_up(max(L, block_b), block_b)
    Mp = round_up(max(M, LANES), LANES)
    rows_p = jnp.pad(rows, (0, Lp - L), constant_values=M)
    cols_p = jnp.pad(cols, (0, Lp - L))
    data_p = jnp.pad(data, (0, Lp - L))
    x_p = jnp.pad(x, (0, Mp - M))
    up, cs = pl.pallas_call(
        functools.partial(_sym_streams_kernel, M=M),
        grid=(Lp // block_b,),
        in_specs=[
            pl.BlockSpec((block_b,), lambda b: (b,)),
            pl.BlockSpec((block_b,), lambda b: (b,)),
            pl.BlockSpec((block_b,), lambda b: (b,)),
            pl.BlockSpec((Mp,), lambda b: (0,)),   # x resident in VMEM
        ],
        out_specs=[
            pl.BlockSpec((block_b,), lambda b: (b,)),
            pl.BlockSpec((block_b,), lambda b: (b,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Lp,), data.dtype),
            jax.ShapeDtypeStruct((Lp,), data.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1,), data.dtype)],
        interpret=interpret,
    )(rows_p, cols_p, data_p, x_p)
    return up[:L], cs[:L]


def _bsr_tiles_kernel(brows_ref, bcols_ref, data_ref, x_ref, out_ref,
                      *, Mb: int):
    rows = brows_ref[...]
    valid = rows < Mb
    xg = x_ref[...][bcols_ref[...]]                      # [Bt, b]
    contrib = jnp.sum(data_ref[...] * xg[:, None, :], axis=2)
    out_ref[...] = jnp.where(valid[:, None], contrib, 0)


@functools.partial(jax.jit, static_argnames=("Mb", "block_t", "interpret"))
def bsr_tiles(brows, bcols, data, xr, *, Mb: int, block_t: int = 4096,
              interpret: bool | None = None):
    """Per-stored-block partial products ``data[k] @ x_block[bcols[k]]``.

    ``xr`` is the dense vector reshaped ``(Nb, b)`` and stays VMEM
    resident; the caller scatter-adds the returned ``[nbmax, b]``
    partials into block rows.  ``bcols`` must be pre-clipped.
    """
    interpret = resolve_interpret(interpret)
    nb, b = data.shape[0], data.shape[1]
    Nb = xr.shape[0]
    block_t = min(block_t, round_up(max(nb, 1), 512))
    nbp = round_up(max(nb, block_t), block_t)
    Nbp = round_up(max(Nb, LANES), LANES)
    brows_p = jnp.pad(brows, (0, nbp - nb), constant_values=Mb)
    bcols_p = jnp.pad(bcols, (0, nbp - nb))
    data_p = jnp.pad(data, ((0, nbp - nb), (0, 0), (0, 0)))
    xr_p = jnp.pad(xr, ((0, Nbp - Nb), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_bsr_tiles_kernel, Mb=Mb),
        grid=(nbp // block_t,),
        in_specs=[
            pl.BlockSpec((block_t,), lambda t: (t,)),
            pl.BlockSpec((block_t,), lambda t: (t,)),
            pl.BlockSpec((block_t, b, b), lambda t: (t, 0, 0)),
            pl.BlockSpec((Nbp, b), lambda t: (0, 0)),  # x resident
        ],
        out_specs=pl.BlockSpec((block_t, b), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((nbp, b), data.dtype),
        interpret=interpret,
    )(brows_p, bcols_p, data_p, xr_p)
    return out[:nb]
