"""End-to-end kernel-backed sparse assembly (the TPU production path).

Composes the Pallas kernels along the paper's part structure and the
two-phase API of :mod:`repro.sparse`:

  Parts 1-3  radix_sort.radix_sort_pair  (multi-digit histogram +
             exclusive scan + placement per 8-11-bit digit — the
             overflow-free replacement for one counting-sort pass per
             matrix dimension)
  Part 4     prefix over column counts (tiny, size N)
  Numeric    segment_sum.gather_segment_sum_sorted — gather-by-perm +
             masked sorted-segment-sum fused into one kernel pass

``plan_pallas`` is the symbolic phase (reusable ``SparsePattern``);
``fill_fused`` is the fused numeric fill; ``fill_pallas`` keeps the
unfused two-kernel reduce for comparison; ``assemble_pallas`` is the
one-shot plan + fused fill; ``multiply_fused`` is the SpGEMM numeric
phase (two resident operand gathers + multiply + reduce in one kernel,
over a ``repro.sparse.spgemm.ProductPattern``).  Tests assert
bit-identical structure vs. the NumPy Matlab oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..core.csc import CSC
from ..sparse.dispatch import sorted_permutation
from ..sparse.pattern import (
    SparsePattern,
    fill_dtype,
    pattern_from_perm,
    trivial_pattern,
)
from ..sparse.sharded import ShardedCSC, ShardedPattern, route_values
from ..sparse.spgemm import ProductPattern
from .segment_sum.ops import (
    accum_dtype,
    gather2_segment_sum_sorted,
    gather_segment_reduce_sorted,
    gather_segment_sum_sorted,
    segment_sum_sorted,
)


@functools.partial(
    jax.jit, static_argnames=("M", "N", "nzmax", "block_b", "interpret")
)
def plan_pallas(
    rows: jax.Array,
    cols: jax.Array,
    *,
    M: int,
    N: int,
    nzmax: int | None = None,
    block_b: int | None = None,
    interpret: bool | None = None,
) -> SparsePattern:
    """Symbolic phase with the radix-partition planner kernels.

    One histogram + placement pass per 8-11-bit digit of the (col, row)
    key — ``ceil(log2 M / bits) + ceil(log2 N / bits)`` data-movement
    passes over L instead of one full pass per matrix dimension, and no
    int32-overflow regime at any size.
    """
    L = rows.shape[0]
    nzmax = L if nzmax is None else nzmax
    if L == 0 or M == 0 or N == 0:
        # Matlab empty-matrix semantics: valid all-zero pattern, no
        # radix passes over an empty (or all-sentinel) stream
        return trivial_pattern(L, (M, N), nzmax=nzmax)
    rows = rows.astype(jnp.int32)
    cols = cols.astype(jnp.int32)
    perm = sorted_permutation(
        rows, cols, M=M, N=N, method="radix",
        block_b=block_b, interpret=interpret,
    )
    return pattern_from_perm(rows, cols, perm, M=M, N=N, nzmax=nzmax)


def fill_fused(
    pattern: SparsePattern,
    vals: jax.Array,
    *,
    accum: str | None = None,
    block_b: int | None = None,
    interpret: bool | None = None,
) -> CSC:
    """Fused numeric phase: gather + mask + segment reduce in one kernel.

    ``fill_pallas`` materializes ``vals[perm]`` to HBM and re-reads it
    inside the scan kernel — two extra float round trips over L.  Here
    the gather-by-perm, the padding mask and the scan (cumsum for
    ``sum``/``mean``, segmented min/max scan otherwise) run in a single
    Pallas kernel; only the O(nzmax) segment-boundary gathers remain
    outside.  Output dtype matches :meth:`SparsePattern.scatter`
    bit-for-bit (the shared ``fill_dtype`` contract, resolved by the
    callee); ``accum=None`` follows the pattern's mode.
    """
    totals = gather_segment_reduce_sorted(
        vals, pattern.perm, pattern.slot,
        accum=pattern.accum if accum is None else accum,
        num_segments=pattern.nzmax, block_b=block_b, interpret=interpret,
    )
    return CSC(
        data=totals,
        indices=pattern.indices,
        indptr=pattern.indptr,
        nnz=pattern.nnz,
        shape=pattern.shape,
    )


def multiply_fused(
    pattern: ProductPattern,
    data_A: jax.Array,
    data_B: jax.Array,
    *,
    block_b: int | None = None,
    interpret: bool | None = None,
) -> CSC:
    """Fused SpGEMM numeric phase: gathers + multiply + reduce in one
    kernel.

    The jnp :meth:`~repro.sparse.spgemm.ProductPattern.multiply` path
    materializes the expansion product stream before its scatter; here
    the two operand gathers, the product, the padding mask and the
    prefix sum run in a single Pallas kernel
    (:func:`~repro.kernels.segment_sum.ops.gather2_segment_sum_sorted`)
    with both operand value vectors VMEM-resident — the same residency
    budget and blocked fallback as :func:`fill_fused`.  Bit-compatible
    dtype contract with ``multiply`` (shared ``fill_dtype`` /
    ``accum_dtype`` rules).
    """
    if data_A.ndim != 1 or data_A.shape[0] != pattern.a_capacity \
            or data_B.ndim != 1 or data_B.shape[0] != pattern.b_capacity:
        raise ValueError(
            f"operand data shapes {data_A.shape}/{data_B.shape} do not "
            f"match the planned 1-d capacities "
            f"({pattern.a_capacity}/{pattern.b_capacity})"
        )
    dtype = jnp.promote_types(data_A.dtype, data_B.dtype)
    totals = gather2_segment_sum_sorted(
        data_A.astype(dtype), data_B.astype(dtype),
        pattern.sa, pattern.sb, pattern.pattern.slot,
        num_segments=pattern.nzmax, block_b=block_b, interpret=interpret,
    )
    return CSC(
        data=totals,
        indices=pattern.pattern.indices,
        indptr=pattern.pattern.indptr,
        nnz=pattern.pattern.nnz,
        shape=pattern.shape,
    )


def fill_pallas(
    pattern: SparsePattern,
    vals: jax.Array,
    *,
    accum: str | None = None,
    interpret: bool | None = None,
) -> CSC:
    """Numeric phase with the *unfused* Pallas sorted-segment-sum.

    Duplicates are adjacent in the plan's sorted stream, so the paper's
    colliding scatter-add becomes a segment sum — deterministic and
    parallel ("reduction ... in a fully independent manner").  Kept as
    the two-kernel baseline; :func:`fill_fused` removes the
    ``vals[perm]`` HBM round trip.  Non-``sum`` accum modes delegate to
    the shared masked sorted-segment reductions.
    """
    accum = pattern.accum if accum is None else accum
    if accum != "sum":
        return fill_fused(pattern, vals, accum=accum, interpret=interpret)
    first = pattern.first
    valid = pattern.slot < pattern.nzmax
    dtype = fill_dtype(vals)
    acc = accum_dtype(dtype)  # 16-bit floats cumsum in f32
    v_s = jnp.where(
        valid, vals[pattern.perm].astype(acc), jnp.zeros((), acc)
    )
    totals = segment_sum_sorted(
        v_s, first, num_segments=pattern.nzmax, interpret=interpret
    ).astype(dtype)
    return CSC(
        data=totals,
        indices=pattern.indices,
        indptr=pattern.indptr,
        nnz=pattern.nnz,
        shape=pattern.shape,
    )


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "axis", "capacity", "nzb", "interpret"),
)
def _fill_sharded_pallas_jit(send_slot, perm, slot, vals, *, mesh, axis,
                             capacity, nzb, interpret):
    p = mesh.shape[axis]

    def _local(send_slot, perm, slot, v):
        buf = route_values(send_slot[0], v, p=p, capacity=capacity,
                           axis=axis)
        data = jax.vmap(
            lambda vv: gather_segment_sum_sorted(
                vv, perm[0], slot[0], num_segments=nzb,
                interpret=interpret,
            )
        )(buf)
        return data[None]

    return shard_map(
        _local,
        mesh=mesh, check_vma=False,
        in_specs=(P(axis), P(axis), P(axis), P(None, axis)),
        out_specs=P(axis),
    )(send_slot, perm, slot, vals)


def fill_sharded_pallas(
    pattern: ShardedPattern,
    vals: jax.Array,
    *,
    interpret: bool | None = None,
) -> ShardedCSC:
    """Numeric phase of a :class:`ShardedPattern` with the kernel tail.

    Same Phase B replay as ``ShardedPattern.assemble`` (bucket scatter +
    one all_to_all on values), but each row block's reduce runs the
    *fused* gather + masked sorted-segment-sum kernel instead of a
    colliding scatter-add — the distributed fill shares the
    single-device production kernels.
    """
    vals = pattern._pad_vals(jnp.asarray(vals))
    data = _fill_sharded_pallas_jit(
        pattern.send_slot, pattern.perm, pattern.slot, vals[None],
        mesh=pattern.mesh, axis=pattern.axis, capacity=pattern.capacity,
        nzb=pattern.nzb, interpret=interpret,
    )
    return pattern._wrap(data[:, 0])


@functools.partial(
    jax.jit, static_argnames=("M", "N", "nzmax", "block_b", "interpret")
)
def assemble_pallas(
    rows: jax.Array,
    cols: jax.Array,
    vals: jax.Array,
    *,
    M: int,
    N: int,
    nzmax: int | None = None,
    block_b: int | None = None,
    interpret: bool | None = None,
) -> CSC:
    """Padded-CSC assembly with all size-L passes in Pallas kernels."""
    pattern = plan_pallas(
        rows, cols, M=M, N=N, nzmax=nzmax,
        block_b=block_b, interpret=interpret,
    )
    return fill_fused(pattern, vals, interpret=interpret)
