"""SpMV kernel for the FEM example: padded-ELL, VMEM-resident x.

CSC is the assembly output, but TPU SpMV wants row-major locality, so
the matrix is converted once (``ops.csc_to_ell``) to ELLPACK: per row a
fixed ``K`` column-index / value slots (padded with ``col = N`` → x
contribution 0).  The kernel tiles rows into blocks; the dense vector
``x`` lives whole in VMEM (FEM vectors at 50k f32 = 200 KB).  Each
invocation gathers ``x[cols]`` for a ``[Br, K]`` tile and reduces along
K — arithmetic intensity ~2 flops / 8 bytes, i.e. memory-bound like
everything in this paper, but with *contiguous* HBM reads only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import resolve_interpret, round_up


def _spmv_ell_kernel(cols_ref, vals_ref, x_ref, y_ref):
    cols = cols_ref[...]          # [Br, K] int32 (N = padding)
    vals = vals_ref[...]          # [Br, K] f32
    x = x_ref[...]                # [Np] f32 (padded with trailing 0)
    xg = x[cols.reshape(-1)].reshape(cols.shape)
    y_ref[...] = jnp.sum(vals * xg, axis=1)


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def spmv_ell(
    cols: jax.Array,
    vals: jax.Array,
    x: jax.Array,
    *,
    block_r: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """y[r] = sum_k vals[r, k] * x[cols[r, k]] with col == len(x) padding."""
    interpret = resolve_interpret(interpret)
    M, K = cols.shape
    N = x.shape[0]
    Mp = round_up(max(M, block_r), block_r)
    Np = round_up(N + 1, 128)
    cols_p = jnp.pad(cols, ((0, Mp - M), (0, 0)), constant_values=N)
    vals_p = jnp.pad(vals, ((0, Mp - M), (0, 0)))
    x_p = jnp.pad(x, (0, Np - N))  # slot N (and beyond) reads 0.0
    y = pl.pallas_call(
        _spmv_ell_kernel,
        grid=(Mp // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, K), lambda r: (r, 0)),
            pl.BlockSpec((block_r, K), lambda r: (r, 0)),
            pl.BlockSpec((Np,), lambda r: (0,)),
        ],
        out_specs=pl.BlockSpec((block_r,), lambda r: (r,)),
        out_shape=jax.ShapeDtypeStruct((Mp,), vals.dtype),
        interpret=interpret,
    )(cols_p, vals_p, x_p)
    return y[:M]
