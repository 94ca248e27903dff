"""Per-digit kernels of the LSD radix-partition planner.

The counting-sort kernels (``kernels/counting_sort``) run one full
histogram + placement pass per *matrix dimension* — ``nbins`` is M+1 or
N+1, so the one-hot tile work grows with the matrix size and huge
matrices need a fused key that overflows int32.  The radix planner
instead sorts the two-word key ``(col, row)`` one bounded *digit* at a
time: every pass looks only at a few bits of one index word, so

  * the padded bin tile is a small constant (usually one 128-lane
    tile) regardless of M and N — no overflow fallback exists, and
  * the number of data-movement passes over L is chosen by an explicit
    cost model (``ops.plan_digit_passes``) instead of being tied to
    the dimension count.

The kernels here are the per-digit versions of the Part-1/Part-2
kernels, with the digit extraction ``(key >> shift) & mask`` fused into
VMEM so the digit stream never round-trips HBM:

  _digit_hist_kernel       private per-block digit histogram
                           (paper Listing 9, block == thread)
  _digit_placement_kernel  the paper's placement loop
                           ``rank[jrS[k]++] = i`` decomposed as
                           global base (Part-1 offsets) + prior-equal
                           count: the block is swept one 128-lane row
                           at a time, the row's [T, 128] one-hot is
                           scanned by a 128 x 128 triangular matmul and
                           a per-bin carry joins the rows — O(B x T)
                           work, no [B, B] equality matrix (the
                           counting-sort kernel's MXU trick costs
                           O(B^2) per block).

Layouts are the ones the TPU compiler accepts: keys and positions as
``[L / 128, 128]`` rows, per-block histograms and offsets as
``[nblocks, Kp, 1]`` columns (bins on sublanes).

Tiles adapt to the digit width: ``block_t`` shrinks to the 128-lane
rounding of ``nbins`` so a 5-bit digit pays for one lane tile, not a
512-wide one.  Padding convention: callers pad the key stream with
``-1``; the histogram maps negatives to an out-of-range sentinel bin so
they count nowhere, and placement positions for padding land beyond the
real stream and are sliced off by ``ops``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import LANES, SUBLANES, resolve_interpret, round_up

#: the element-block granule: keys and positions are laid out as
#: ``[L / 128, 128]`` rows, and a block spans whole (8, 128) int32 tiles.
_GRANULE = SUBLANES * LANES


def _tile_width(nbins: int, block_t: int) -> int:
    """Bin-tile width for a digit with ``nbins`` bins: never wider than
    the requested ``block_t``, never narrower than one 128-lane tile."""
    return min(block_t, round_up(nbins, LANES))


def _layout(L: int, nbins: int, block_b: int, block_t: int):
    """``(block_b, block_t, Lp, Kp)``: element block rounded to whole
    tiles, bin tile, and the padded stream and bin lengths."""
    block_t = _tile_width(nbins, block_t)
    block_b = round_up(max(block_b, 1), _GRANULE)
    Lp = round_up(max(L, block_b), block_b)
    Kp = round_up(max(nbins, block_t), block_t)
    return block_b, block_t, Lp, Kp


def _extract_digit(keys, *, shift: int, mask: int, sentinel: int):
    """``(keys >> shift) & mask``, with negative (padding) keys routed
    to the out-of-range ``sentinel`` bin."""
    d = (keys >> shift) & jnp.int32(mask)
    return jnp.where(keys < 0, jnp.int32(sentinel), d)


def _tile_bins(block_t: int):
    """Column ``[block_t, 1]`` of the bin ids of grid tile ``t``."""
    t = pl.program_id(1)
    return t * block_t + jax.lax.broadcasted_iota(jnp.int32, (block_t, 1), 0)


def _digit_hist_kernel(keys_ref, out_ref, *, shift: int, mask: int,
                       block_t: int, sentinel: int):
    """out[b, t0:t0+T] = histogram of block b's digits over bin tile t.

    The block is swept one 128-lane row at a time: each row's
    ``[T, 128]`` one-hot (bins on sublanes, elements on lanes) is added
    into a running tile, reduced over lanes once at the end.
    """
    bins = _tile_bins(block_t)

    def row(r, acc):
        d = _extract_digit(keys_ref[pl.ds(r, 1), :], shift=shift,
                           mask=mask, sentinel=sentinel)
        return acc + (d == bins).astype(jnp.int32)

    acc = jax.lax.fori_loop(0, keys_ref.shape[0], row,
                            jnp.zeros((block_t, LANES), jnp.int32))
    out_ref[...] = jnp.sum(acc, axis=1, keepdims=True)


def _digit_placement_kernel(keys_ref, offsets_ref, pos_ref, *, shift: int,
                            mask: int, block_t: int, sentinel: int):
    """Grid (nblocks, ntiles): each tile adds its digits' contribution.

    For element i with digit in this tile:
      position[i] = offsets[b, digit_i]          (global base + earlier
                                                  blocks, from Part 1)
                  + prior_equal_in_block(i)      (running count of equal
                                                  digits before i)
    The running count is an inclusive scan of the ``[T, 128]`` one-hot
    of one 128-lane row — a matmul with the upper-triangular ones
    matrix on the MXU, exact for 0/1 inputs — plus a per-bin carry over
    earlier rows (the scan's last lane).  Digits outside the tile
    contribute zero, so summing over the grid's tile axis assembles the
    full position.
    """
    bins = _tile_bins(block_t)
    j = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    upper = (j <= i).astype(jnp.bfloat16)

    @pl.when(pl.program_id(1) == 0)
    def _():
        pos_ref[...] = jnp.zeros_like(pos_ref)

    def row(r, carry):
        d = _extract_digit(keys_ref[pl.ds(r, 1), :], shift=shift,
                           mask=mask, sentinel=sentinel)
        onehot = d == bins
        incl = jnp.dot(onehot.astype(jnp.bfloat16), upper,
                       preferred_element_type=jnp.float32
                       ).astype(jnp.int32)
        # element i's slot: base + equal digits before it = carry+incl-1
        slot = jnp.where(onehot, carry + incl - 1, 0)
        pos_ref[pl.ds(r, 1), :] += jnp.sum(slot, axis=0, keepdims=True)
        return carry + incl[:, LANES - 1:]

    jax.lax.fori_loop(0, keys_ref.shape[0], row,
                      offsets_ref[...].astype(jnp.int32))


@functools.partial(
    jax.jit,
    static_argnames=("shift", "bits", "nbins", "block_b", "block_t",
                     "interpret"),
)
def digit_block_histogram(
    keys: jax.Array,
    *,
    shift: int,
    bits: int,
    nbins: int,
    block_b: int = 1024,
    block_t: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-block digit histograms ``[nblocks, nbins_padded]``."""
    L = keys.shape[0]
    block_b, block_t, Lp, Kp = _layout(L, nbins, block_b, block_t)
    keys_p = jnp.pad(keys, (0, Lp - L), constant_values=-1)
    nblocks = Lp // block_b
    rows = block_b // LANES
    hist = pl.pallas_call(
        functools.partial(
            _digit_hist_kernel, shift=shift, mask=(1 << bits) - 1,
            block_t=block_t, sentinel=Kp,
        ),
        grid=(nblocks, Kp // block_t),
        in_specs=[pl.BlockSpec((rows, LANES), lambda b, t: (b, 0))],
        out_specs=pl.BlockSpec((None, block_t, 1), lambda b, t: (b, t, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks, Kp, 1), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(keys_p.reshape(Lp // LANES, LANES))
    return hist.reshape(nblocks, Kp)


@functools.partial(
    jax.jit,
    static_argnames=("shift", "bits", "nbins", "block_b", "block_t",
                     "interpret"),
)
def digit_placement(
    keys: jax.Array,
    offsets: jax.Array,
    *,
    shift: int,
    bits: int,
    nbins: int,
    block_b: int = 1024,
    block_t: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """positions[i] such that a stable digit sort lands element i there.

    ``offsets``: ``[nblocks, nbins]`` per-block exclusive offsets (from
    ``ops.radix_pass_rank`` with the *same* ``block_b``).  Only the
    first ``len(keys)`` positions are meaningful; padding placements are
    sliced off by the caller.
    """
    L = keys.shape[0]
    block_b, block_t, Lp, Kp = _layout(L, nbins, block_b, block_t)
    keys_p = jnp.pad(keys, (0, Lp - L), constant_values=-1)
    nblocks = Lp // block_b
    rows = block_b // LANES
    offs_p = jnp.pad(
        offsets.astype(jnp.int32),
        ((0, nblocks - offsets.shape[0]), (0, Kp - offsets.shape[1])),
    )
    pos = pl.pallas_call(
        functools.partial(
            _digit_placement_kernel, shift=shift, mask=(1 << bits) - 1,
            block_t=block_t, sentinel=Kp,
        ),
        grid=(nblocks, Kp // block_t),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda b, t: (b, 0)),
            pl.BlockSpec((None, block_t, 1), lambda b, t: (b, t, 0)),
        ],
        out_specs=pl.BlockSpec((rows, LANES), lambda b, t: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((Lp // LANES, LANES), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(keys_p.reshape(Lp // LANES, LANES), offs_p.reshape(nblocks, Kp, 1))
    return pos.reshape(Lp)[:L]
