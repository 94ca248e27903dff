"""Part-1 kernel: blocked histogram of bounded integer keys.

The paper's Listing 9 gives each *thread* a private counter array and
accumulates hierarchically.  Here each *grid block* is the thread: an
invocation at grid point ``(b, t)`` counts the keys of input block ``b``
that fall into bin tile ``t``, writing a private ``[T]`` counter row —
no atomics, exactly the paper's trick.  The cross-block accumulation
(the "accumulate jrS over the threads" loop) is a tree reduction done
by the caller (``ops.histogram``).

VMEM per invocation: keys block ``B`` int32 + a ``B x T`` one-hot
compare tile + a ``T`` counter row.  Defaults ``B=1024, T=512`` give
~2.3 MB — comfortably inside the ~16 MB v5e VMEM with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import resolve_interpret, round_up


def _hist_kernel(keys_ref, out_ref, *, block_t: int):
    """out[b, t0:t0+T] = histogram of keys block b over bin tile t."""
    t = pl.program_id(1)
    keys = keys_ref[...]  # [B] int32
    bins = t * block_t + jax.lax.iota(jnp.int32, block_t)  # [T]
    # one-hot compare tile: [B, T]; sum over the block axis -> [T]
    onehot = (keys[:, None] == bins[None, :]).astype(jnp.int32)
    out_ref[...] = jnp.sum(onehot, axis=0, keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("nbins", "block_b", "block_t", "interpret")
)
def block_histogram(
    keys: jax.Array,
    *,
    nbins: int,
    block_b: int = 1024,
    block_t: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-block histograms ``[nblocks, nbins_padded]`` (private counters)."""
    interpret = resolve_interpret(interpret)
    L = keys.shape[0]
    Lp = round_up(max(L, block_b), block_b)
    Kp = round_up(max(nbins, block_t), block_t)
    keys_p = jnp.pad(keys, (0, Lp - L), constant_values=Kp)  # pad -> out of range
    nblocks = Lp // block_b
    out = pl.pallas_call(
        functools.partial(_hist_kernel, block_t=block_t),
        grid=(nblocks, Kp // block_t),
        in_specs=[pl.BlockSpec((block_b,), lambda b, t: (b,))],
        out_specs=pl.BlockSpec((1, block_t), lambda b, t: (b, t)),
        out_shape=jax.ShapeDtypeStruct((nblocks, Kp), jnp.int32),
        interpret=interpret,
    )(keys_p)
    return out
