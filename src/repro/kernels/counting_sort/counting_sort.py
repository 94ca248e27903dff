"""Part-2 kernel: distribution-counting-sort *placement*.

The paper's serial placement loop (Listing 5)

    for (i = 0; i < len; i++) rank[jrS[ii[i]]++] = i;

has a loop-carried dependence through the ``++``.  The TPU-native
decomposition (DESIGN.md §2) splits the counter into three terms:

    position[i] =  jr[key_i]                  (global base, from Part 1)
                +  prior_blocks[b, key_i]      (elements in earlier blocks)
                +  prior_equal_in_block(i)     (elements earlier in block b)

The first two are the per-block offsets computed by ``hist.ops
.block_offsets`` (the paper's thread-private ``jrS[k]``).  The third is
where the MXU earns its keep: with ``E[x,y] = (key_x == key_y)`` and a
strictly-lower-triangular mask ``T``, ``prior_equal = row_sum(E * T)``
— an elementwise product + reduction over a ``[B, B]`` tile.

The base gather ``offsets[b, key_i]`` is likewise computed without any
dynamic gather: one-hot(keys) @ offsets-tile, an ``[B, T] x [T]``
matvec accumulated over bin tiles — exact in f32 for values < 2^24.

Output is the *position* array; the final ``rank[position[i]] = i`` is
a unique-index scatter (a permutation — collision-free, fully parallel)
left to XLA by ``ops.counting_sort``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import resolve_interpret, round_up


def _placement_kernel(keys_ref, offsets_ref, pos_ref, *, block_t: int):
    """Grid (nblocks, ntiles): tile 0 seeds prior-equal + base, others add."""
    t = pl.program_id(1)
    keys = keys_ref[...]
    B = keys.shape[0]
    bins = t * block_t + jax.lax.iota(jnp.int32, block_t)
    onehot = (keys[:, None] == bins[None, :]).astype(jnp.float32)
    base = jnp.dot(
        onehot, offsets_ref[0, :].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)

    @pl.when(t == 0)
    def _():
        eq = (keys[:, None] == keys[None, :]).astype(jnp.int32)
        ii = jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
        prior_equal = jnp.sum(eq * (jj < ii).astype(jnp.int32), axis=1)
        pos_ref[...] = prior_equal + base

    @pl.when(t != 0)
    def _():
        pos_ref[...] = pos_ref[...] + base


@functools.partial(
    jax.jit, static_argnames=("nbins", "block_b", "block_t", "interpret")
)
def placement(
    keys: jax.Array,
    offsets: jax.Array,
    *,
    nbins: int,
    block_b: int = 1024,
    block_t: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """positions[i] such that ``rank[positions[i]] = i`` counting-sorts keys.

    ``offsets``: ``[nblocks, nbins]`` from ``hist.ops.block_offsets``
    with the *same* ``block_b``.
    """
    interpret = resolve_interpret(interpret)
    L = keys.shape[0]
    Lp = round_up(max(L, block_b), block_b)
    Kp = round_up(max(nbins, block_t), block_t)
    keys_p = jnp.pad(keys, (0, Lp - L), constant_values=Kp - 1)
    nblocks = Lp // block_b
    offs_p = jnp.pad(
        offsets.astype(jnp.int32),
        ((0, nblocks - offsets.shape[0]), (0, Kp - offsets.shape[1])),
    )
    pos = pl.pallas_call(
        functools.partial(_placement_kernel, block_t=block_t),
        grid=(nblocks, Kp // block_t),
        in_specs=[
            pl.BlockSpec((block_b,), lambda b, t: (b,)),
            pl.BlockSpec((1, block_t), lambda b, t: (b, t)),
        ],
        out_specs=pl.BlockSpec((block_b,), lambda b, t: (b,)),
        out_shape=jax.ShapeDtypeStruct((Lp,), jnp.int32),
        interpret=interpret,
    )(keys_p, offs_p)
    return pos[:L]
