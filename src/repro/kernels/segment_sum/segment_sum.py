"""Part-3/4 + post-processing kernel: blocked prefix scan with carry.

After the counting-sort passes, duplicates are *adjacent* in the value
stream, so the paper's colliding scatter-add (Listing 14/17) becomes a
segmented reduction over a sorted stream.  The only non-elementwise
ingredient is a *global cumulative sum* — implemented here as a blocked
Pallas scan: TPU grid steps execute **in order** on a core, so a
scratch VMEM cell carries the running total across blocks (the Pallas
idiom that replaces the paper's serial "accumulate over threads" loop).

``ops.segment_sum_sorted`` then extracts per-segment totals with two
contiguous gathers — no random scatter ever touches HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import LANES, resolve_interpret, round_up


def _cumsum_kernel(x_ref, out_ref, carry_ref):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    x = x_ref[...]
    c = jnp.cumsum(x)
    out_ref[...] = c + carry_ref[0]
    carry_ref[0] = carry_ref[0] + c[-1]


def _gather_cumsum_kernel(perm_ref, slot_ref, vals_ref, out_ref, carry_ref,
                          *, nzmax: int):
    """Fused numeric-phase head: gather-by-perm + mask + carry cumsum.

    The unfused path writes ``vals[perm]`` back to HBM and re-reads it
    in the cumsum kernel — two full float round trips over L.  Here the
    value vector stays resident (one input block spanning all grid
    steps) and each grid step gathers its permuted slice directly in
    VMEM, masks padding (``slot >= nzmax``), and extends the running
    prefix sum — the gathered stream never exists in HBM.
    """
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    vals = vals_ref[...]
    v = vals[perm_ref[...]]
    v = jnp.where(slot_ref[...] < nzmax, v, jnp.zeros((), v.dtype))
    c = jnp.cumsum(v)
    out_ref[...] = c + carry_ref[0]
    carry_ref[0] = carry_ref[0] + c[-1]


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def blocked_cumsum(
    x: jax.Array, *, block_b: int = 4096, interpret: bool | None = None
) -> jax.Array:
    """Inclusive prefix sum via sequential-grid carry scan."""
    interpret = resolve_interpret(interpret)
    L = x.shape[0]
    Lp = round_up(max(L, block_b), block_b)
    xp = jnp.pad(x, (0, Lp - L))
    out = pl.pallas_call(
        _cumsum_kernel,
        grid=(Lp // block_b,),
        in_specs=[pl.BlockSpec((block_b,), lambda b: (b,))],
        out_specs=pl.BlockSpec((block_b,), lambda b: (b,)),
        out_shape=jax.ShapeDtypeStruct((Lp,), x.dtype),
        scratch_shapes=[pltpu.VMEM((1,), x.dtype)],
        interpret=interpret,
    )(xp)
    return out[:L]


def _gather_segscan_kernel(perm_ref, slot_ref, first_ref, vals_ref,
                           out_ref, carry_ref, *, nzmax: int, op: str):
    """Fused gather + mask + *segmented* scan (min/max) with carry.

    The cumsum trick of :func:`_gather_cumsum_kernel` extracts segment
    totals as differences of a global running sum — that only works for
    an invertible monoid.  min/max are not invertible, so the reduction
    is an inclusive **segmented** scan instead: a (value, started) pair
    combined with ``combine((a, fa), (b, fb)) = (b if fb else op(a, b),
    fa | fb)`` — associative, so the within-block scan is a
    Hillis-Steele ladder (log2(block) shift+select steps, all in VMEM)
    and the cross-block carry is just the last full-prefix value (its
    flag can never be consumed: the carry is the leftmost operand).
    Masked (``slot >= nzmax``) elements carry the op identity, so
    padding between segments passes the running value through; the
    per-segment reduction is then the scan value at each segment's last
    element (gathered by the caller).
    """
    b = pl.program_id(0)
    vals = vals_ref[...]
    ident = jnp.array(
        jnp.inf if op == "min" else -jnp.inf, vals.dtype
    )
    fn = jnp.minimum if op == "min" else jnp.maximum

    @pl.when(b == 0)
    def _():
        carry_ref[...] = jnp.full_like(carry_ref, ident)

    v = vals[perm_ref[...]]
    v = jnp.where(slot_ref[...] < nzmax, v, ident)
    f = first_ref[...] != 0
    n = v.shape[0]
    d = 1
    while d < n:  # static unroll: log2(block_b) shift+select steps
        pv = jnp.concatenate([jnp.full((d,), ident, v.dtype), v[:-d]])
        pf = jnp.concatenate([jnp.zeros((d,), jnp.bool_), f[:-d]])
        v = jnp.where(f, v, fn(pv, v))
        f = jnp.logical_or(f, pf)
        d *= 2
    out = jnp.where(f, v, fn(carry_ref[0], v))
    out_ref[...] = out
    carry_ref[0] = out[-1]


@functools.partial(
    jax.jit, static_argnames=("num_segments", "op", "block_b", "interpret")
)
def gather_masked_segscan(
    vals: jax.Array,
    perm: jax.Array,
    slot: jax.Array,
    first: jax.Array,
    *,
    num_segments: int,
    op: str,
    block_b: int = 65536,
    interpret: bool | None = None,
) -> jax.Array:
    """Inclusive segmented min/max scan of ``vals[perm]`` masked by
    ``slot < num_segments``, segments delimited by ``first`` flags.

    Same residency contract as :func:`gather_masked_cumsum`: the value
    vector stays VMEM-resident across grid steps, so the only HBM
    traffic over L is one read of ``vals``/``perm``/``slot``/``first``
    and one write of the scan.
    """
    interpret = resolve_interpret(interpret)
    L = perm.shape[0]
    block_b = min(block_b, round_up(max(L, 1), 4096))
    Lp = round_up(max(L, block_b), block_b)
    Lv = round_up(max(vals.shape[0], LANES), LANES)
    vals_p = jnp.pad(vals, (0, Lv - vals.shape[0]))
    # padding gathers element 0 but is masked to the identity by the
    # sentinel slot; padded first-flags are 0, so the carry flows through
    perm_p = jnp.pad(perm, (0, Lp - L))
    slot_p = jnp.pad(slot, (0, Lp - L), constant_values=num_segments)
    first_p = jnp.pad(first.astype(jnp.int32), (0, Lp - L))
    out = pl.pallas_call(
        functools.partial(
            _gather_segscan_kernel, nzmax=num_segments, op=op
        ),
        grid=(Lp // block_b,),
        in_specs=[
            pl.BlockSpec((block_b,), lambda b: (b,)),
            pl.BlockSpec((block_b,), lambda b: (b,)),
            pl.BlockSpec((block_b,), lambda b: (b,)),
            pl.BlockSpec((Lv,), lambda b: (0,)),
        ],
        out_specs=pl.BlockSpec((block_b,), lambda b: (b,)),
        out_shape=jax.ShapeDtypeStruct((Lp,), vals.dtype),
        scratch_shapes=[pltpu.VMEM((1,), vals.dtype)],
        interpret=interpret,
    )(perm_p, slot_p, first_p, vals_p)
    return out[:L]


def _gather2_cumsum_kernel(sa_ref, sb_ref, slot_ref, va_ref, vb_ref,
                           out_ref, carry_ref, *, nzmax: int):
    """Fused SpGEMM numeric head: two gathers + multiply + carry cumsum.

    The expansion product ``va[sa[k]] * vb[sb[k]]`` of the sorted
    SpGEMM stream never exists in HBM: both operand value vectors stay
    VMEM-resident across grid steps (like :func:`_gather_cumsum_kernel`
    keeps its one vector), each step gathers its slice of both, forms
    the product, masks padding (``slot >= nzmax``) and extends the
    running prefix sum.
    """
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    va = va_ref[...]
    vb = vb_ref[...]
    v = va[sa_ref[...]] * vb[sb_ref[...]]
    v = jnp.where(slot_ref[...] < nzmax, v, jnp.zeros((), v.dtype))
    c = jnp.cumsum(v)
    out_ref[...] = c + carry_ref[0]
    carry_ref[0] = carry_ref[0] + c[-1]


@functools.partial(
    jax.jit, static_argnames=("num_segments", "block_b", "interpret")
)
def gather2_masked_cumsum(
    vals_a: jax.Array,
    vals_b: jax.Array,
    sa: jax.Array,
    sb: jax.Array,
    slot: jax.Array,
    *,
    num_segments: int,
    block_b: int = 65536,
    interpret: bool | None = None,
) -> jax.Array:
    """``cumsum(where(slot < num_segments, vals_a[sa] * vals_b[sb], 0))``
    in one kernel pass.

    Same residency contract as :func:`gather_masked_cumsum`, with TWO
    resident operand vectors (callers budget ``vals_a`` + ``vals_b``
    against ``ops.FUSED_RESIDENT_MAX_BYTES`` together).  ``vals_a`` and
    ``vals_b`` must share a dtype (the caller resolves the promotion).
    """
    interpret = resolve_interpret(interpret)
    L = sa.shape[0]
    block_b = min(block_b, round_up(max(L, 1), 4096))
    Lp = round_up(max(L, block_b), block_b)
    La = round_up(max(vals_a.shape[0], LANES), LANES)
    Lb = round_up(max(vals_b.shape[0], LANES), LANES)
    va_p = jnp.pad(vals_a, (0, La - vals_a.shape[0]))
    vb_p = jnp.pad(vals_b, (0, Lb - vals_b.shape[0]))
    # padding gathers element 0 of both but is masked by the sentinel
    sa_p = jnp.pad(sa, (0, Lp - L))
    sb_p = jnp.pad(sb, (0, Lp - L))
    slot_p = jnp.pad(slot, (0, Lp - L), constant_values=num_segments)
    out = pl.pallas_call(
        functools.partial(_gather2_cumsum_kernel, nzmax=num_segments),
        grid=(Lp // block_b,),
        in_specs=[
            pl.BlockSpec((block_b,), lambda b: (b,)),
            pl.BlockSpec((block_b,), lambda b: (b,)),
            pl.BlockSpec((block_b,), lambda b: (b,)),
            pl.BlockSpec((La,), lambda b: (0,)),
            pl.BlockSpec((Lb,), lambda b: (0,)),
        ],
        out_specs=pl.BlockSpec((block_b,), lambda b: (b,)),
        out_shape=jax.ShapeDtypeStruct((Lp,), vals_a.dtype),
        scratch_shapes=[pltpu.VMEM((1,), vals_a.dtype)],
        interpret=interpret,
    )(sa_p, sb_p, slot_p, va_p, vb_p)
    return out[:L]


@functools.partial(
    jax.jit, static_argnames=("num_segments", "block_b", "interpret")
)
def gather_masked_cumsum(
    vals: jax.Array,
    perm: jax.Array,
    slot: jax.Array,
    *,
    num_segments: int,
    block_b: int = 65536,
    interpret: bool | None = None,
) -> jax.Array:
    """``cumsum(where(slot < num_segments, vals[perm], 0))`` in one pass.

    The value vector is kept resident across grid steps (for TPU that
    means it must fit in VMEM alongside one index/output block —
    callers cap the resident buffer at ``ops.FUSED_RESIDENT_MAX_BYTES``
    = 8 MB on a 16 MB core; the Table 4.2 streams fit with
    room to spare), so the only HBM traffic over L is one read of
    ``vals``, one read of ``perm``/``slot``, and one write of the
    prefix sum.
    The default block is much larger than ``blocked_cumsum``'s because
    the resident value vector is re-staged per grid step in interpret
    mode — fewer, bigger steps keep that overhead sublinear; short
    streams clamp down so they never pad up to a full block.
    """
    interpret = resolve_interpret(interpret)
    L = perm.shape[0]
    block_b = min(block_b, round_up(max(L, 1), 4096))
    Lp = round_up(max(L, block_b), block_b)
    Lv = round_up(max(vals.shape[0], LANES), LANES)
    vals_p = jnp.pad(vals, (0, Lv - vals.shape[0]))
    # padding gathers element 0 but is masked by the sentinel slot
    perm_p = jnp.pad(perm, (0, Lp - L))
    slot_p = jnp.pad(slot, (0, Lp - L), constant_values=num_segments)
    out = pl.pallas_call(
        functools.partial(_gather_cumsum_kernel, nzmax=num_segments),
        grid=(Lp // block_b,),
        in_specs=[
            pl.BlockSpec((block_b,), lambda b: (b,)),
            pl.BlockSpec((block_b,), lambda b: (b,)),
            pl.BlockSpec((Lv,), lambda b: (0,)),
        ],
        out_specs=pl.BlockSpec((block_b,), lambda b: (b,)),
        out_shape=jax.ShapeDtypeStruct((Lp,), vals.dtype),
        scratch_shapes=[pltpu.VMEM((1,), vals.dtype)],
        interpret=interpret,
    )(perm_p, slot_p, vals_p)
    return out[:L]
