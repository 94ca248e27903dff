"""Sharded two-phase assembly — the paper's §3 with a plan/fill split.

The parallel paper keeps thread-private counters, one barrier, and a
row-block redistribution so dedup and reduction are lock-free.  PR 1
gave the *single-device* path the two-phase treatment (symbolic
``SparsePattern`` once, O(L) numeric fills many times); this module
gives the *distributed* path the same split, so repeated assembly over
a fixed sparsity structure pays the symbolic analysis and the routing
analysis exactly once:

Plan time (``plan_sharded`` — runs the paper's Parts 1-2 at device
granularity, then Parts 1-4 per block):

  Phase A (paper Part 1 / Listing 9, devices instead of threads):
      per-device histogram over the row-*block* keys, accumulated
      across devices (``psum``/``all_gather`` == the "accumulate jrS
      over the threads" loop), then an exclusive scan over the device
      index gives each device its private base offsets into every
      destination block's logical stream (``send_base``).

  Phase B (row-block redistribution, symbolic):
      device d owns rows ``[d*rpb, (d+1)*rpb)``.  A capacity-bounded
      ``all_to_all`` routes every triplet's *indices* to its row-block
      owner; the per-input send-bucket slot (``send_slot``) is captured
      so the numeric phase can replay the exchange on values alone.
      Overflowing a capacity bucket is detected and reported.

  Phase C (paper Parts 2-4 per block):
      each device runs the serial symbolic analysis (``plan``) on its
      received row block — the captured per-block :class:`SparsePattern`
      arrays (perm/slot/indices/indptr/nnz) are baked into the
      :class:`ShardedPattern`.

Fill time (``ShardedPattern.assemble`` / ``assemble_batch``):
      O(L/p) per device — scatter values into the precomputed send
      buckets, one ``all_to_all``, one collision-free gather+scatter
      through the block pattern.  No histogram, no sort, no routing
      analysis.

The output :class:`ShardedCSC` is block-row partitioned, registered in
the :mod:`repro.sparse.formats` registry (so ``convert(A, "csc")`` /
``to_dense``/``find`` work uniformly) and carries its mesh so
``A.spmv(x)`` / ``A @ x`` reuse the shared per-block CSC kernel tail
under ``shard_map``.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..core.coo import COO
from ..core.csc import CSC, slot_columns
from ..core.csc import spmv as csc_spmv
from .dispatch import resolve_method
from .pattern import fill_dtype, plan


def resolve_mesh(mesh: Mesh | None = None, *, axis: str = "data") -> Mesh:
    """Default mesh for ``method="sharded"``: one axis over all devices."""
    if mesh is not None:
        return mesh
    from ..launch.mesh import make_data_mesh

    return make_data_mesh(axis=axis)


def mesh_fingerprint(mesh: Mesh, axis: str) -> tuple:
    """Hashable identity of a mesh for host-side plan caches."""
    return (
        tuple(mesh.axis_names),
        tuple(mesh.shape[a] for a in mesh.axis_names),
        tuple(d.id for d in mesh.devices.flat),
        axis,
    )


# ---------------------------------------------------------------------------
# ShardedCSC — the block-row partitioned output format
# ---------------------------------------------------------------------------
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedCSC:
    """Block-row partitioned CSC: leading axis = device shards.

    data    : float[p, nzb] values (``[p, B, nzb]`` from assemble_batch —
              use :meth:`batch_select` to view one batch element)
    indices : int32[p, nzb] *local* row within the block; ``rpb`` = padding
    indptr  : int32[p, N+1]
    nnz     : int32[p] per-block nnz (blocks partition the rows, so the
              per-block counts sum to the global structural nnz)
    shape   : (M, N) static
    mesh    : optional static Mesh + axis name — carried by the sharded
              assembly path so ``spmv`` can rebuild its ``shard_map``
    """

    data: jax.Array
    indices: jax.Array
    indptr: jax.Array
    nnz: jax.Array
    shape: tuple[int, int] = dataclasses.field(metadata=dict(static=True))
    mesh: Mesh | None = dataclasses.field(
        default=None, metadata=dict(static=True)
    )
    axis: str = dataclasses.field(default="data", metadata=dict(static=True))

    @property
    def n_blocks(self) -> int:
        return int(self.data.shape[0])

    @property
    def rows_per_block(self) -> int:
        return -(-self.shape[0] // self.n_blocks)

    @property
    def nzb(self) -> int:
        """Per-block slot capacity."""
        return int(self.data.shape[-1])

    def batch_select(self, b: int) -> "ShardedCSC":
        """View batch element ``b`` of an ``assemble_batch`` result."""
        if self.data.ndim != 3:
            raise ValueError("batch_select needs batched data [p, B, nzb]")
        return dataclasses.replace(self, data=self.data[:, b])

    def block(self, b: int) -> CSC:
        """Row block ``b`` as a standalone (rpb, N) padded CSC."""
        if self.data.ndim != 2:
            raise ValueError(
                "batched ShardedCSC ([p, B, nzb] data from assemble_batch); "
                "select one element with batch_select(b) first"
            )
        return CSC(
            data=self.data[b],
            indices=self.indices[b],
            indptr=self.indptr[b],
            nnz=self.nnz[b],
            shape=(self.rows_per_block, self.shape[1]),
        )

    def to_dense(self) -> jax.Array:
        M, _ = self.shape
        blocks = [self.block(b).to_dense() for b in range(self.n_blocks)]
        return jnp.concatenate(blocks, axis=0)[:M]

    # -- linear algebra ----------------------------------------------------
    def spmv(self, x: jax.Array) -> jax.Array:
        """y = A @ x: per-block shared CSC kernel tail under shard_map.

        ``x`` is replicated (columns are global); each device computes
        its owned row block with the same :func:`repro.core.csc.spmv`
        the single-device path uses, so kernel improvements are shared.
        """
        if self.mesh is None:
            raise ValueError(
                "this ShardedCSC carries no mesh; rebuild it through "
                "plan_sharded(...).assemble(...) so spmv knows its "
                "device layout"
            )
        if self.data.ndim != 2:
            raise ValueError("spmv needs unbatched data; see batch_select")
        return _sharded_spmv(
            self.data, self.indices, self.indptr, self.nnz, x,
            mesh=self.mesh, axis=self.axis, shape=self.shape,
        )

    def __matmul__(self, x: jax.Array) -> jax.Array:
        return self.spmv(x)


@partial(jax.jit, static_argnames=("mesh", "axis", "shape"))
def _sharded_spmv(data, indices, indptr, nnz, x, *, mesh, axis, shape):
    M, N = shape
    p = data.shape[0]
    rpb = -(-M // p)

    def _local(d, i, ip, nz, xv):
        blk = CSC(data=d[0], indices=i[0], indptr=ip[0], nnz=nz[0],
                  shape=(rpb, N))
        return csc_spmv(blk, xv)[None]

    y = shard_map(
        _local,
        mesh=mesh, check_vma=False,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P()),
        out_specs=P(axis),
    )(data, indices, indptr, nnz, x)
    return y.reshape(-1)[:M]


# ---------------------------------------------------------------------------
# ShardedPattern — the distributed symbolic plan
# ---------------------------------------------------------------------------
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedPattern:
    """Distributed assembly plan: routing metadata + per-block patterns.

    All leading axes are the device axis ``p``.  ``send_slot`` replays
    Phase B on values alone; ``perm``/``slot``/``indices``/``indptr``/
    ``nnz`` are each block's captured :class:`SparsePattern` arrays
    (Phase C); ``send_base``/``block_load``/``overflow`` are the Phase A
    products (exclusive device scan, arrivals per block, capacity check).
    """

    send_slot: jax.Array   # int32[p, L_loc]; p*capacity marks dropped inputs
    perm: jax.Array        # int32[p, R]   (R = p*capacity received slots)
    slot: jax.Array        # int32[p, R]; nzb marks dropped entries
    indices: jax.Array     # int32[p, nzb]; rpb sentinel in padded tail
    indptr: jax.Array      # int32[p, N+1]
    nnz: jax.Array         # int32[p] per-block structural nnz
    send_base: jax.Array   # int32[p, p] exclusive scan over device index
    block_load: jax.Array  # int32[p, p] arrivals per row block (psum'd,
                           # so every device row is identical)
    overflow: jax.Array    # bool[p] any send bucket over capacity
    shape: tuple[int, int] = dataclasses.field(metadata=dict(static=True))
    L: int = dataclasses.field(metadata=dict(static=True))  # input length
    capacity: int = dataclasses.field(metadata=dict(static=True))
    mesh: Mesh = dataclasses.field(metadata=dict(static=True))
    axis: str = dataclasses.field(default="data", metadata=dict(static=True))

    # -- static geometry ---------------------------------------------------
    @property
    def p(self) -> int:
        return int(self.send_slot.shape[0])

    @property
    def L_pad(self) -> int:
        """Padded input length (divisible by p)."""
        return int(self.send_slot.shape[0] * self.send_slot.shape[1])

    @property
    def rpb(self) -> int:
        return -(-self.shape[0] // self.p)

    @property
    def nzb(self) -> int:
        return int(self.indices.shape[-1])

    def nnz_total(self) -> jax.Array:
        return jnp.sum(self.nnz)

    def any_overflow(self) -> jax.Array:
        return jnp.any(self.overflow)

    # -- numeric phase -----------------------------------------------------
    def assemble(self, vals: jax.Array) -> ShardedCSC:
        """O(L/p) fill: bucket scatter + one all_to_all + block scatter.

        Differentiable: the fill carries a ``custom_vjp`` whose backward
        replays the Phase-B routing *transposed* (gather-by-slot per
        block, the involutive ``all_to_all``, send-bucket gather) — see
        :func:`_route_fill`.
        """
        vals = self._pad_vals(vals)
        data = _fill_sharded(
            self.send_slot, self.perm, self.slot, vals[None],
            mesh=self.mesh, axis=self.axis, capacity=self.capacity,
            nzb=self.nzb, squeeze=True,
        )
        return self._wrap(data)

    def assemble_batch(self, vals_batch: jax.Array) -> ShardedCSC:
        """Batched fill sharing this structure: ``vals_batch`` is [B, L].

        The result's ``data`` is ``[p, B, nzb]`` (the block axis must
        stay leading — it is the sharded one); everything else is
        unbatched.  Use :meth:`ShardedCSC.batch_select` per element.
        """
        if vals_batch.ndim != 2:
            raise ValueError("assemble_batch expects [B, L] values")
        vals_batch = self._pad_vals(vals_batch)
        data = _fill_sharded(
            self.send_slot, self.perm, self.slot, vals_batch,
            mesh=self.mesh, axis=self.axis, capacity=self.capacity,
            nzb=self.nzb, squeeze=False,
        )
        return self._wrap(data)

    def update(self, add_rows, add_cols, drop_mask=None, **kwargs):
        """Structural deltas are not yet routed per row block.

        The dispatch seam exists so facade code can call ``.update`` on
        any pattern type, but an incremental merge would have to rewrite
        every block's local stream *and* the cross-device routing
        tables; until that lands, re-plan with :func:`plan_sharded`
        over the concatenated triplets, or assemble unsharded
        (``method=None``) and use :meth:`SparsePattern.update`.
        """
        raise NotImplementedError(
            "ShardedPattern.update: incremental deltas are not yet "
            "routed per row block — re-plan with plan_sharded(...) over "
            "the concatenated triplets, or assemble unsharded and use "
            "SparsePattern.update"
        )

    def _pad_vals(self, vals: jax.Array) -> jax.Array:
        if vals.shape[-1] != self.L:
            raise ValueError(
                f"vals has length {vals.shape[-1]} but this pattern was "
                f"planned for L={self.L} triplets"
            )
        pad = self.L_pad - self.L
        if pad:
            widths = [(0, 0)] * (vals.ndim - 1) + [(0, pad)]
            vals = jnp.pad(vals, widths)
        return vals

    def _wrap(self, data: jax.Array) -> ShardedCSC:
        return ShardedCSC(
            data=data, indices=self.indices, indptr=self.indptr,
            nnz=self.nnz, shape=self.shape, mesh=self.mesh, axis=self.axis,
        )


# ---------------------------------------------------------------------------
# Plan time — Phases A, B (symbolic), C
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("shape", "mesh", "axis", "capacity",
                                   "nzb", "method"))
def _plan_sharded_jit(rows, cols, *, shape, mesh, axis, capacity, nzb,
                      method):
    M, N = shape
    p = mesh.shape[axis]
    rpb = -(-M // p)
    L_loc = rows.shape[0] // p
    drop = p * capacity

    def _local(rows, cols):
        pad = rows >= M
        dest = jnp.minimum(rows // rpb, p - 1)
        key = jnp.where(pad, p, dest).astype(jnp.int32)

        # Phase A — Part 1 at device granularity: per-device histogram
        # over row-block keys, accumulated across devices; the exclusive
        # scan over the device index yields this device's private base
        # offset into every destination's logical arrival stream.
        counts = jnp.bincount(key, length=p + 1)[:p].astype(jnp.int32)
        gathered = jax.lax.all_gather(counts, axis)          # [p_src, p]
        me = jax.lax.axis_index(axis)
        before = jnp.arange(p, dtype=jnp.int32)[:, None] < me
        send_base = jnp.sum(jnp.where(before, gathered, 0), axis=0)
        block_load = jnp.sum(gathered, axis=0)               # arrivals/block
        overflow = jnp.any(counts > capacity)

        # Phase B (symbolic) — capacity-bounded routing: a stable
        # counting sort by destination assigns each input its fixed
        # send-bucket slot; the slot map is the only thing the numeric
        # phase needs to replay the exchange.
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        k_s = key[order]
        start = jnp.searchsorted(
            k_s, jnp.arange(p, dtype=k_s.dtype)
        ).astype(jnp.int32)
        offset = (
            jnp.arange(L_loc, dtype=jnp.int32)
            - start[jnp.minimum(k_s, p - 1)]
        )
        ok = jnp.logical_and(k_s < p, offset < capacity)
        flat = jnp.where(ok, k_s * capacity + offset, drop)
        send_slot = (
            jnp.full((L_loc,), drop, jnp.int32)
            .at[order]
            .set(flat)
        )

        def route(x, fill):
            buf = (
                jnp.full((drop,), fill, x.dtype)
                .at[send_slot]
                .set(x, mode="drop")
            )
            return jax.lax.all_to_all(
                buf.reshape(p, capacity), axis, 0, 0, tiled=True
            ).ravel()

        r_recv = route(rows.astype(jnp.int32), jnp.int32(M))
        c_recv = route(cols.astype(jnp.int32), jnp.int32(0))
        r_loc = jnp.where(r_recv >= M, rpb, r_recv - me * rpb)
        r_loc = jnp.clip(r_loc, 0, rpb).astype(jnp.int32)

        # Phase C — the serial symbolic analysis (Parts 1-4) on the
        # owned row block; identical code path as the single-device plan.
        pat = plan(r_loc, c_recv, (rpb, N), nzmax=nzb, method=method)
        return (
            send_slot[None], pat.perm[None], pat.slot[None],
            pat.indices[None], pat.indptr[None], pat.nnz[None],
            send_base[None], block_load[None], overflow[None],
        )

    return shard_map(
        _local,
        mesh=mesh, check_vma=False,
        in_specs=(P(axis), P(axis)),
        out_specs=tuple([P(axis)] * 9),
    )(rows, cols)


def plan_sharded(
    rows,
    cols,
    shape: tuple[int, int],
    *,
    mesh: Mesh | None = None,
    axis: str = "data",
    capacity: int | None = None,
    capacity_factor: float = 2.0,
    nzmax: int | None = None,
    method: str | None = None,
    symmetric: bool = False,
) -> ShardedPattern:
    """Run Phases A-C once; capture a reusable :class:`ShardedPattern`.

    ``rows``/``cols`` are zero-offset global index vectors of length L
    (``row == shape[0]`` marks padding); they are padded to a multiple
    of the device count internally.  ``capacity`` bounds each
    (source, destination) all_to_all bucket (default
    ``capacity_factor * L_pad / p**2``, rounded up to a multiple of 8);
    ``nzmax`` is the per-block slot capacity (default: the per-block
    received length ``p * capacity``).  ``method`` selects the *local*
    sort backend used by each block's Phase C (``None`` -> the
    backend-aware production default; on TPU that is the Pallas radix
    planner, so the same kernels serve the single-device and per-shard
    sorts).

    ``symmetric=True`` requests the halved strict-upper plan
    (``plan_symmetric``'s contract) — not implemented for the sharded
    path: the block-row partition would need a mirrored-entry router
    so each half-entry reaches both owning blocks.  The request is
    rejected *clearly* here instead of silently planning (and
    streaming) the full mirrored stream twice.
    """
    if symmetric:
        raise NotImplementedError(
            "plan_sharded(symmetric=True) is not supported: the "
            "block-row partition has no mirrored-entry router yet, so "
            "a symmetric plan would silently stream the full structure "
            "twice; fall back to the plain-CSC sharded plan "
            "(symmetric=False), or use plan_symmetric on one device"
        )
    method = resolve_method(method)
    mesh = resolve_mesh(mesh, axis=axis)
    M, N = int(shape[0]), int(shape[1])
    p = mesh.shape[axis]
    rows = jnp.asarray(rows, jnp.int32)
    cols = jnp.asarray(cols, jnp.int32)
    L = int(rows.shape[0])
    L_pad = -(-max(L, 1) // p) * p
    if L_pad != L:
        rows = jnp.pad(rows, (0, L_pad - L), constant_values=M)
        cols = jnp.pad(cols, (0, L_pad - L))
    if capacity is None:
        capacity = int(capacity_factor * L_pad / (p * p)) + 8
        capacity = -(-capacity // 8) * 8
    nzb = p * capacity if nzmax is None else int(nzmax)
    (send_slot, perm, slot, indices, indptr, nnz, send_base, block_load,
     overflow) = _plan_sharded_jit(
        rows, cols, shape=(M, N), mesh=mesh, axis=axis,
        capacity=int(capacity), nzb=nzb, method=method,
    )
    return ShardedPattern(
        send_slot=send_slot, perm=perm, slot=slot, indices=indices,
        indptr=indptr, nnz=nnz, send_base=send_base,
        block_load=block_load, overflow=overflow, shape=(M, N), L=L,
        capacity=int(capacity), mesh=mesh, axis=axis,
    )


def plan_sharded_coo(coo: COO, **kwargs) -> ShardedPattern:
    """``plan_sharded`` over a :class:`repro.core.COO` container."""
    return plan_sharded(coo.rows, coo.cols, coo.shape, **kwargs)


# ---------------------------------------------------------------------------
# Fill time — the O(L/p) numeric phase
# ---------------------------------------------------------------------------
def route_values(send_slot, v, *, p: int, capacity: int, axis: str):
    """Replay Phase B on values alone (per device, under shard_map).

    ``send_slot`` is one device's captured bucket map ``int32[L_loc]``;
    ``v`` is ``[B, L_loc]``.  One bucket scatter + one all_to_all gives
    the ``[B, p*capacity]`` received-value stream that the block
    pattern's gather/scatter (or the kernel-backed segment sum in
    :func:`repro.kernels.assembly_ops.fill_sharded_pallas`) consumes.
    """
    drop = p * capacity
    dtype = fill_dtype(v)
    v = v.astype(dtype)
    buf = (
        jnp.zeros((v.shape[0], drop), dtype)
        .at[:, send_slot]
        .set(v, mode="drop")
    )
    buf = jax.lax.all_to_all(
        buf.reshape(v.shape[0], p, capacity), axis, 1, 1, tiled=True
    )
    return buf.reshape(v.shape[0], drop)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _route_fill(mesh, axis, capacity, nzb, send_slot, perm, slot, vals):
    """Sharded numeric phase with an explicit shard_map-transpose VJP.

    Forward (per device): bucket scatter -> one tiled ``all_to_all`` ->
    collision-free gather+scatter through the block pattern.  The
    backward is the exact transpose of that routing, replayed on
    cotangents: gather-by-slot through the block pattern (set through
    ``perm``, a permutation of the received stream), the *same* tiled
    ``all_to_all`` (the (source, chunk) block transpose is an
    involution, so it is its own transpose), and a padding-masked
    gather out of the send buckets — O(L/p) per device, no re-routing
    analysis and no XLA transpose-of-scatter.
    """
    p = mesh.shape[axis]

    def _local(send_slot, perm, slot, v):
        buf = route_values(send_slot[0], v, p=p, capacity=capacity,
                           axis=axis)
        data = (
            jnp.zeros((v.shape[0], nzb), buf.dtype)
            .at[:, slot[0]]
            .add(buf[:, perm[0]], mode="drop")
        )
        return data[None]

    return shard_map(
        _local,
        mesh=mesh, check_vma=False,
        in_specs=(P(axis), P(axis), P(axis), P(None, axis)),
        out_specs=P(axis),
    )(send_slot, perm, slot, vals)


def _route_fill_fwd(mesh, axis, capacity, nzb, send_slot, perm, slot, vals):
    out = _route_fill(mesh, axis, capacity, nzb, send_slot, perm, slot, vals)
    return out, (send_slot, perm, slot)


def _route_fill_bwd(mesh, axis, capacity, nzb, res, g):
    send_slot, perm, slot = res
    p = mesh.shape[axis]
    drop = p * capacity

    def _local(send_slot, perm, slot, g):
        gb = g[0]                               # [B, nzb] own block's ct
        keep = slot[0] < nzb
        g_recv = jnp.where(
            keep[None, :], gb[:, jnp.clip(slot[0], 0, nzb - 1)],
            jnp.zeros((), gb.dtype),
        )
        g_buf = (
            jnp.zeros((gb.shape[0], drop), gb.dtype)
            .at[:, perm[0]]
            .set(g_recv)                        # perm is a permutation
        )
        g_buf = jax.lax.all_to_all(             # involution: own transpose
            g_buf.reshape(gb.shape[0], p, capacity), axis, 1, 1, tiled=True
        ).reshape(gb.shape[0], drop)
        sent = send_slot[0] < drop
        return jnp.where(
            sent[None, :], g_buf[:, jnp.clip(send_slot[0], 0, drop - 1)],
            jnp.zeros((), g_buf.dtype),
        )

    g_vals = shard_map(
        _local,
        mesh=mesh, check_vma=False,
        in_specs=(P(axis), P(axis), P(axis), P(axis)),
        out_specs=P(None, axis),
    )(send_slot, perm, slot, g)
    return (None, None, None, g_vals)


_route_fill.defvjp(_route_fill_fwd, _route_fill_bwd)


@partial(jax.jit, static_argnames=("mesh", "axis", "capacity", "nzb",
                                   "squeeze"))
def _fill_sharded(send_slot, perm, slot, vals, *, mesh, axis, capacity,
                  nzb, squeeze):
    data = _route_fill(mesh, axis, capacity, nzb, send_slot, perm, slot,
                       vals)
    if squeeze:
        data = data[:, 0]
    return data


# ---------------------------------------------------------------------------
# Format-registry integration (COO is the hub format)
# ---------------------------------------------------------------------------
def sharded_to_coo(A: ShardedCSC) -> COO:
    """Per-block triplets with rows rebased to global coordinates."""
    if A.data.ndim != 2:
        raise ValueError("convert() needs unbatched data; see batch_select")
    M, N = A.shape
    rpb = A.rows_per_block
    rows, cols, vals = [], [], []
    for b in range(A.n_blocks):
        c = slot_columns(A.indptr[b], A.nzb)
        valid = A.indices[b] < rpb
        rows.append(
            jnp.where(valid, A.indices[b] + b * rpb, M).astype(jnp.int32)
        )
        cols.append(jnp.where(valid, jnp.clip(c, 0, N - 1), 0).astype(jnp.int32))
        vals.append(jnp.where(valid, A.data[b], 0.0))
    return COO(
        rows=jnp.concatenate(rows),
        cols=jnp.concatenate(cols),
        vals=jnp.concatenate(vals),
        shape=A.shape,
    )


def coo_to_sharded(A: COO, *, mesh: Mesh | None = None,
                   **plan_kwargs) -> ShardedCSC:
    """Hub conversion: plan + fill (kwargs forward to ``plan_sharded``)."""
    pat = plan_sharded(A.rows, A.cols, A.shape, mesh=mesh, **plan_kwargs)
    if bool(pat.any_overflow()):
        raise ValueError(
            "sharded routing bucket overflow during convert(); pass a "
            "larger capacity_factor/capacity (forwarded to plan_sharded)"
        )
    return pat.assemble(A.vals)


def _register() -> None:
    from .formats import register_converter, register_format

    register_format("sharded", ShardedCSC)
    register_converter(ShardedCSC, "coo", sharded_to_coo)
    register_converter(COO, "sharded", coo_to_sharded)


_register()
