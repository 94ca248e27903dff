"""Two-phase assembly: symbolic ``SparsePattern`` plans + numeric fills.

The paper's intermediate format (§2.3, eq. 2.2-2.3) exists precisely so
that the expensive index analysis can run **once** while the numeric
scatter/reduce is redone many times — the dominant FEM pattern, where
the mesh (hence the sparsity structure) is fixed and only element
values change.

``plan(rows, cols, shape)`` runs Parts 1-4 once and captures everything
the numeric phase needs:

  perm    : int32[L]      (col,row)-ordered traversal permutation
                          (= the paper's ``rank[rank2]`` composition)
  slot    : int32[L]      output slot of the k-th element of the sorted
                          stream (the parallel paper's ``irankP``,
                          eq. 3.1); padding entries point at ``nzmax``
                          so one ``mode="drop"`` scatter discards them
  indices : int32[nzmax]  final CSC row indices ``irS`` (structure is
                          value-independent, so it is baked at plan time)
  indptr  : int32[N+1]    accumulated column pointer ``jcS``
  nnz     : int32 scalar  structural nonzero count

``SparsePattern.assemble(vals)`` is then only the O(L) gather +
collision-free scatter-reduce — no sorting, no histogramming:

    data = zeros(nzmax).at[slot].add(vals[perm], mode="drop")

Beyond the paper, the numeric phase is **transform-native**:

* it carries a ``jax.custom_vjp`` whose backward is the O(L)
  *gather-by-slot* through the stored plan — ``g_vals[perm[k]] =
  w_k * g_data[slot[k]]`` with padding (``slot == nzmax``) masked —
  so ``jax.grad``/``jax.vjp``/``jax.vmap`` compose through ``scatter``/
  ``assemble``/``assemble_batch``/``reduce_rows`` with no re-sort and
  no transpose-of-scatter.  Higher-order *reverse* mode (grad-of-grad)
  works — the backward is plain jnp — but ``jax.custom_vjp`` excludes
  forward-mode AD by JAX's design, so ``jax.jvp``/``jax.jacfwd``
  through a fill raises ``TypeError`` (use reverse mode, the training
  loop's direction);
* duplicates can combine under any ``accum`` mode in :data:`ACCUM_MODES`
  (``"sum"`` is Matlab ``sparse``; the others are ``accumarray``-style
  reductions over each duplicate group, applied in stable input order
  for ``"first"``/``"last"``).

The dataclass is pytree-registered with ``shape`` and ``accum`` static,
so plans pass freely through ``jax.jit`` / ``jax.vmap`` / ``lax.scan``
carries.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..core.coo import COO
from ..core.csc import CSC
from .dispatch import merge_search, sorted_permutation
from .errors import CapacityWarning

#: duplicate-combination modes of the numeric phase.  ``"sum"`` is the
#: Matlab ``sparse`` contract; the rest mirror ``accumarray`` with
#: ``@min``/``@max``/``@mean`` and positional selection in stable input
#: order (``"first"``/``"last"``).  Slots with no valid input (the
#: padded tail) hold structural zeros under every mode.
ACCUM_MODES = ("sum", "min", "max", "mean", "first", "last")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SparsePattern:
    """Symbolic assembly plan — the paper's intermediate format, cached.

    All array fields are length-``L`` or length-``nzmax`` with static
    shapes; ``row == M`` input sentinels were already routed to the
    drop slot, so the numeric phase needs no masking branches.

    ``srows``/``scols`` carry the sorted ``(col, row)`` key stream
    (``rows[perm]``/``cols[perm]``, padding sentinels included) — the
    state :meth:`update` merges a sorted delta against without
    re-sorting the survivors.  ``epoch`` is a static structure-version
    counter: value-only changes never retrace a jitted consumer, while
    an :meth:`update` bumps it so dependent caches (plan LRU, SpGEMM
    products, AOT executables) can tell a rewritten structure from the
    one they compiled against.
    """

    perm: jax.Array     # int32[L]
    slot: jax.Array     # int32[L]; nzmax marks dropped (padding) inputs
    indices: jax.Array  # int32[nzmax]; M sentinel in the padded tail
    indptr: jax.Array   # int32[N+1]
    nnz: jax.Array      # int32 scalar
    srows: jax.Array    # int32[L]; sorted row keys (= rows[perm])
    scols: jax.Array    # int32[L]; sorted col keys (= cols[perm])
    shape: tuple[int, int] = dataclasses.field(metadata=dict(static=True))
    accum: str = dataclasses.field(
        default="sum", metadata=dict(static=True)
    )
    epoch: int = dataclasses.field(
        default=0, metadata=dict(static=True)
    )

    # -- static geometry --------------------------------------------------
    @property
    def L(self) -> int:
        return int(self.perm.shape[-1])

    @property
    def nzmax(self) -> int:
        return int(self.indices.shape[-1])

    @property
    def M(self) -> int:
        return int(self.shape[0])

    @property
    def N(self) -> int:
        return int(self.shape[1])

    # -- paper-fidelity views ---------------------------------------------
    @property
    def first(self) -> jax.Array:
        """Boundary flags of the sorted stream (Part 3 output)."""
        return first_flags(self.slot, self.nzmax)

    def irank(self) -> jax.Array:
        """Original-input-order output slots — the paper's eq. (2.2-2.3)."""
        return jnp.zeros((self.L,), jnp.int32).at[self.perm].set(
            jnp.minimum(self.slot, self.nzmax - 1)
        )

    # -- numeric phase ----------------------------------------------------
    def assemble(self, vals: jax.Array, *, accum: str | None = None) -> CSC:
        """Numeric fill: O(L) gather + collision-free scatter-reduce.

        ``vals`` must be the value vector aligned with the ``rows``/
        ``cols`` this plan was built from (length L, any float dtype).
        Differentiable: ``jax.grad``/``jax.vjp`` through the result's
        ``data`` run the O(L) gather-by-slot backward (no re-sort).
        ``accum`` overrides the plan's duplicate-combination mode.
        """
        data = self.scatter(vals, accum=accum)
        return CSC(
            data=data,
            indices=self.indices,
            indptr=self.indptr,
            nnz=self.nnz,
            shape=self.shape,
        )

    def assemble_batch(self, vals_batch: jax.Array,
                       *, accum: str | None = None) -> CSC:
        """Vectorized fill of many value vectors sharing this structure.

        Returns a :class:`CSC` whose ``data`` carries a leading batch
        axis ``[B, nzmax]`` while ``indices``/``indptr``/``nnz`` stay
        unbatched (the structure is shared by construction).  Consume
        with ``jax.vmap(f, in_axes=(CSC(data=0, indices=None, ...),))``
        or by indexing ``out.data[b]``.
        """
        data = jax.vmap(lambda v: self.scatter(v, accum=accum))(vals_batch)
        return CSC(
            data=data,
            indices=self.indices,
            indptr=self.indptr,
            nnz=self.nnz,
            shape=self.shape,
        )

    def scatter(self, vals: jax.Array, *, accum: str | None = None
                ) -> jax.Array:
        """The raw O(L) numeric kernel: ``data`` array only (``prS``).

        Differentiable (``custom_vjp``): the backward pass is the O(L)
        gather-by-slot through this plan, padding-masked — no re-sort.
        """
        accum = validate_accum(self.accum if accum is None else accum,
                               vals.dtype)
        if vals.ndim != 1 or vals.shape[0] != self.L:
            raise ValueError(
                f"vals has shape {vals.shape} but this pattern was "
                f"planned for a length-L={self.L} vector; use "
                "assemble_batch/vmap for batched fills"
            )
        dtype = fill_dtype(vals)
        with jax.named_scope("fill"):
            return _scatter_vjp(
                self.nzmax, accum, self.perm, self.slot, vals.astype(dtype)
            )

    def reduce_rows(self, mat: jax.Array, *, accum: str | None = None
                    ) -> jax.Array:
        """Segment-reduce a row-per-triplet matrix ``[L, D] -> [nzmax, D]``.

        The generalization of :meth:`scatter` to vector-valued triplets
        (e.g. embedding-gradient rows); duplicates of the same (i, j)
        pair combine row-wise (elementwise for min/max) into one slot
        under the plan's ``accum`` mode, like every other fill.
        Differentiable via the same gather-by-slot ``custom_vjp`` as
        :meth:`scatter` (so e.g. the embedding-gradient assembly in
        ``repro.train.sparse_grads`` is itself twice-differentiable);
        dtype passes through unchanged — hence min/max require an
        inexact dtype (their ±inf identity has no integer encoding).
        """
        accum = validate_accum(self.accum if accum is None else accum,
                               mat.dtype)
        if accum in ("min", "max") \
                and not jnp.issubdtype(mat.dtype, jnp.inexact):
            raise ValueError(
                f"reduce_rows(accum={accum!r}) needs an inexact dtype "
                f"(got {mat.dtype}); cast the rows first"
            )
        if mat.shape[0] != self.L:
            raise ValueError(
                f"mat has {mat.shape[0]} rows but this pattern was "
                f"planned for L={self.L} triplets"
            )
        return _scatter_vjp(self.nzmax, accum, self.perm, self.slot, mat)

    # -- incremental symbolic phase ---------------------------------------
    def _input_keys(self) -> tuple[np.ndarray, np.ndarray]:
        """Original input-order (rows, cols), reconstructed host-side.

        ``perm`` is a permutation of the input stream and ``srows``/
        ``scols`` are its sorted image, so one scatter inverts exactly —
        the full re-plan fallback of :meth:`update` rebuilds the
        concatenated triplet stream from this.
        """
        perm = np.asarray(self.perm)
        rows = np.empty((self.L,), np.int32)
        cols = np.empty((self.L,), np.int32)
        rows[perm] = np.asarray(self.srows)
        cols[perm] = np.asarray(self.scols)
        return rows, cols

    def update(
        self,
        add_rows,
        add_cols,
        drop_mask=None,
        *,
        nzmax: int | None = None,
        method: str | None = None,
        merge_method: str | None = None,
    ) -> "SparsePattern":
        """Incremental re-plan: merge a delta stream into this plan.

        ``add_rows``/``add_cols`` are zero-offset index vectors of new
        triplets (``row == M`` marks padding, exactly like :func:`plan`);
        ``drop_mask`` is an optional boolean vector over the *original
        input order* (length L) marking triplets to remove.  The result
        is **bit-identical** to a fresh ``plan()`` over the concatenated
        (surviving + delta) stream — for every registered sort backend —
        but only the O(L_delta log L_delta) delta is sorted: the
        surviving sorted stream is kept and the delta is positioned by
        the merge-by-key search (``merge_method=``, see
        ``repro.sparse.dispatch``; the Pallas kernel lives in
        ``repro.kernels.merge``), then ``perm``/``slot``/``indices``/
        ``indptr`` are rewritten in O(L + L_delta).

        Capacity: an explicit ``nzmax=`` wins; otherwise the plan's own
        ``nzmax`` is kept while the merged stream fits, and once the
        headroom is exhausted the call degrades to a full re-plan with a
        one-time :class:`RuntimeWarning` (pre-reserve headroom with
        ``plan(..., nzmax_slack=)`` to stay on the merge path).  An
        empty update (no delta, no effective drops) returns ``self``
        unchanged — no kernel launch, no epoch bump.  Updating a
        trivial (empty/zero-dim) plan degrades to a plain ``plan()``.
        The returned pattern's ``epoch`` is ``self.epoch + 1``.
        """
        M, N = self.M, self.N
        L = self.L
        ar = np.asarray(add_rows)
        ac = np.asarray(add_cols)
        if ar.ndim != 1 or ar.shape != ac.shape:
            raise ValueError(
                f"add_rows/add_cols must be equal-length 1-d vectors; "
                f"got shapes {ar.shape} and {ac.shape}"
            )
        ar = ar.astype(np.int32)
        ac = ac.astype(np.int32)
        L_delta = int(ar.shape[0])
        dm = None
        if drop_mask is not None:
            dm = np.asarray(drop_mask)
            if dm.shape != (L,):
                raise ValueError(
                    f"drop_mask has shape {dm.shape} but this pattern "
                    f"was planned for L={L} input triplets"
                )
            dm = dm.astype(bool)
            if not dm.any():
                dm = None
        n_drop = 0 if dm is None else int(dm.sum())
        if L_delta == 0 and n_drop == 0:
            return self
        L_keep = L - n_drop
        L_new = L_keep + L_delta
        headroom = max(0, self.nzmax - L)
        if nzmax is not None:
            new_nzmax = int(nzmax)
            fallback = False
        elif L_new <= self.nzmax:
            new_nzmax = self.nzmax
            fallback = False
        else:
            new_nzmax = L_new + headroom
            fallback = True
        bump = dict(accum=self.accum, epoch=self.epoch + 1)
        if L_new == 0:
            return _maybe_validated(dataclasses.replace(
                trivial_pattern(0, (M, N), nzmax=new_nzmax), **bump
            ))
        if L == 0 or M == 0 or N == 0:
            # trivial base: nothing to merge against (an empty stream)
            # or a zero-dim shape where structure is key-independent —
            # degrade to a plain plan() over the concatenated stream
            rows0, cols0 = self._input_keys()
            keep = slice(None) if dm is None else ~dm
            pat = plan(
                jnp.asarray(np.concatenate([rows0[keep], ar])),
                jnp.asarray(np.concatenate([cols0[keep], ac])),
                (M, N), nzmax=new_nzmax, method=method,
            )
            return _maybe_validated(dataclasses.replace(pat, **bump))
        if fallback:
            global _UPDATE_FALLBACK_WARNED
            if not _UPDATE_FALLBACK_WARNED:
                _UPDATE_FALLBACK_WARNED = True
                warnings.warn(
                    f"SparsePattern.update: the merged stream "
                    f"(L={L_new}) exceeds this plan's nzmax="
                    f"{self.nzmax} growth headroom — falling back to a "
                    "full re-plan over the concatenated triplets. "
                    "Pre-reserve capacity with plan(..., nzmax_slack=) "
                    "(or fsparse/sparse2 nzmax_slack=) to keep updates "
                    "on the O(L + L_delta) merge path.",
                    CapacityWarning,
                    stacklevel=2,
                )
            rows0, cols0 = self._input_keys()
            keep = slice(None) if dm is None else ~dm
            pat = plan(
                jnp.asarray(np.concatenate([rows0[keep], ar])),
                jnp.asarray(np.concatenate([cols0[keep], ac])),
                (M, N), nzmax=new_nzmax, method=method,
            )
            return _maybe_validated(dataclasses.replace(pat, **bump))
        # -- merge path: survivors stay sorted, only the delta sorts ----
        if dm is None:
            sr_a, sc_a, pa = self.srows, self.scols, self.perm
        else:
            # drops have data-dependent survivor counts: compact on the
            # host.  New input position of survivor p is p minus the
            # dropped positions below it (the fresh concatenated stream
            # the merge must stay bit-identical to renumbers this way).
            perm_np = np.asarray(self.perm).astype(np.int64)
            shift = np.concatenate(
                [[0], np.cumsum(dm.astype(np.int64))[:-1]]
            )
            keep_sorted = ~dm[perm_np]
            pa = jnp.asarray(
                (perm_np - shift[perm_np])[keep_sorted].astype(np.int32)
            )
            sr_a = jnp.asarray(np.asarray(self.srows)[keep_sorted])
            sc_a = jnp.asarray(np.asarray(self.scols)[keep_sorted])
        pat = _merge_sorted_streams(
            sr_a, sc_a, pa, jnp.asarray(ar), jnp.asarray(ac),
            jnp.int32(L_keep), M=M, N=N, nzmax=new_nzmax,
            method=method, merge_method=merge_method,
        )
        return _maybe_validated(dataclasses.replace(pat, **bump))


def fill_dtype(vals) -> jnp.dtype:
    """Numeric-phase value dtype contract.

    Complex/float dtypes pass through bit-exact (Matlab sparse is
    double or complex); integer values are promoted once to f32, not
    silently truncated.  The single home of this rule —
    :meth:`SparsePattern.scatter`, the kernel fills
    (``repro.kernels.assembly_ops`` / ``segment_sum``), the sharded
    value routing and the operator re-plans (``repro.sparse.ops.add``)
    all resolve through here so the paths cannot drift.  Accepts an
    array or a dtype-like.
    """
    dtype = jnp.dtype(getattr(vals, "dtype", vals))
    return dtype if jnp.issubdtype(dtype, jnp.inexact) else jnp.float32


def accum_dtype(dtype) -> jnp.dtype:
    """Duplicate-accumulator dtype for a value dtype.

    A bf16/f16 running sum saturates once the total passes ~256 (1 +
    256 == 256 in bf16), whether the sum is a global cumsum (the kernel
    fills) or a per-slot scatter-add chain (the jnp fills) — so 16-bit
    floats accumulate in f32 everywhere and the O(nzmax) totals are
    cast back to the value dtype.  Single-homed here next to
    :func:`fill_dtype` so the jnp scatter path and the Pallas kernels
    (``repro.kernels.segment_sum``) cannot drift apart.
    """
    dtype = jnp.dtype(dtype)
    if dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16)):
        return jnp.dtype(jnp.float32)
    return dtype


def first_flags(slot: jax.Array, nzmax: int) -> jax.Array:
    """Boundary flags of a sorted stream from its output-slot array.

    ``slot >= nzmax`` marks dropped (padding) entries; the first
    occurrence of every kept slot starts a segment.  The single home of
    this convention — :attr:`SparsePattern.first` and the kernel-backed
    sharded fill (``repro.kernels.assembly_ops``) both derive their
    segment structure here.
    """
    valid = slot < nzmax
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), slot[:-1]])
    return jnp.logical_and(valid, slot != prev)


def last_flags(slot: jax.Array, nzmax: int) -> jax.Array:
    """Last-occurrence flags of each kept slot in the sorted stream.

    The mirror of :func:`first_flags`; valid because duplicates of one
    (i, j) pair are adjacent (padding never interrupts an equal-key run
    — its ``row == M`` sentinel is a distinct sort key).
    """
    valid = slot < nzmax
    nxt = jnp.concatenate([slot[1:], jnp.full((1,), -1, jnp.int32)])
    return jnp.logical_and(valid, slot != nxt)


def validate_accum(accum: str, dtype=None) -> str:
    """Check an ``accum`` mode name (and its dtype compatibility)."""
    if accum not in ACCUM_MODES:
        raise ValueError(
            f"unknown accum mode {accum!r}; expected one of {ACCUM_MODES}"
        )
    if dtype is not None and accum in ("min", "max") \
            and jnp.issubdtype(jnp.dtype(dtype), jnp.complexfloating):
        raise ValueError(
            f"accum={accum!r} is undefined for complex values "
            "(no total order); use 'sum'/'mean'/'first'/'last'"
        )
    return accum


def accum_identity(accum: str, dtype) -> jax.Array:
    """Neutral element of an ``accum`` mode for ``dtype`` (inexact)."""
    if accum == "min":
        return jnp.array(jnp.inf, dtype)
    if accum == "max":
        return jnp.array(-jnp.inf, dtype)
    return jnp.zeros((), dtype)


def _slot_counts(nzmax: int, slot: jax.Array) -> jax.Array:
    """Valid duplicate count per output slot (padding auto-dropped)."""
    return (
        jnp.zeros((nzmax,), jnp.int32)
        .at[slot]
        .add(jnp.int32(1), mode="drop")
    )


def _bcast(mask: jax.Array, ndim: int) -> jax.Array:
    """Right-pad a 1-d mask with singleton axes up to ``ndim`` dims."""
    return mask.reshape(mask.shape + (1,) * (ndim - 1))


def _scatter_reduce(nzmax: int, accum: str, perm, slot, vals):
    """Numeric phase, any accum mode: pure-jnp scatter reductions.

    ``vals`` is ``[L, ...]`` (already dtype-resolved); the result is
    ``[nzmax, ...]``.  This is the jnp fallback of the masked
    sorted-segment reductions (the Pallas streams live in
    ``repro.kernels.segment_sum``); both meet the same contract.
    """
    v = vals[perm]
    out_shape = (nzmax,) + v.shape[1:]
    acc = accum_dtype(v.dtype)  # 16-bit floats accumulate in f32
    if accum == "sum":
        return (
            jnp.zeros(out_shape, acc)
            .at[slot]
            .add(v.astype(acc), mode="drop")
            .astype(v.dtype)
        )
    if accum in ("min", "max"):
        ident = accum_identity(accum, v.dtype)
        ref = jnp.full(out_shape, ident, v.dtype).at[slot]
        red = ref.min(v, mode="drop") if accum == "min" \
            else ref.max(v, mode="drop")
        occupied = _bcast(_slot_counts(nzmax, slot) > 0, red.ndim)
        return jnp.where(occupied, red, jnp.zeros((), v.dtype))
    if accum == "mean":
        s = jnp.zeros(out_shape, acc).at[slot].add(
            v.astype(acc), mode="drop"
        )
        n = jnp.maximum(_slot_counts(nzmax, slot), 1).astype(acc)
        return (s / _bcast(n, s.ndim)).astype(v.dtype)
    if accum == "first":
        keep = first_flags(slot, nzmax)
    else:  # "last"
        keep = last_flags(slot, nzmax)
    return (
        jnp.zeros(out_shape, v.dtype)
        .at[jnp.where(keep, slot, nzmax)]
        .set(v, mode="drop")
    )


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _scatter_vjp(nzmax: int, accum: str, perm, slot, vals):
    """Differentiable numeric phase (forward == :func:`_scatter_reduce`).

    Every accum mode's output is ``data[s] = Σ_k w_k · v_k`` for
    per-element weights ``w`` (1 for sum, 1/count for mean, a 0/1
    selection for min/max/first/last), so one backward rule covers all
    modes: ``g_vals[perm[k]] = w_k · g_data[slot[k]]`` — an O(L)
    padding-masked gather-by-slot plus one collision-free scatter
    through ``perm`` (a permutation).  No re-sort, no XLA
    transpose-of-scatter.  min/max use the subgradient that routes to
    the *first* attaining element of each duplicate group
    (deterministic tie-break).
    """
    return _scatter_reduce(nzmax, accum, perm, slot, vals)


def _scatter_vjp_fwd(nzmax, accum, perm, slot, vals):
    out = _scatter_reduce(nzmax, accum, perm, slot, vals)
    # min/max need the attained value to recompute the winner in bwd;
    # every other mode's weights derive from slot alone (kept O(L)-lean
    # so the forward fill pays nothing when not differentiated).
    res = (perm, slot, vals, out) if accum in ("min", "max") \
        else (perm, slot)
    return out, res


def _scatter_vjp_bwd(nzmax, accum, res, g):
    perm, slot = res[0], res[1]
    L = perm.shape[0]
    valid = slot < nzmax
    slot_c = jnp.clip(slot, 0, nzmax - 1)
    g_sorted = jnp.where(_bcast(valid, g.ndim), g[slot_c],
                         jnp.zeros((), g.dtype))
    if accum == "mean":
        n = jnp.maximum(_slot_counts(nzmax, slot), 1).astype(g.dtype)
        g_sorted = g_sorted / _bcast(n[slot_c], g.ndim)
    elif accum == "first":
        g_sorted = jnp.where(_bcast(first_flags(slot, nzmax), g.ndim),
                             g_sorted, jnp.zeros((), g.dtype))
    elif accum == "last":
        g_sorted = jnp.where(_bcast(last_flags(slot, nzmax), g.ndim),
                             g_sorted, jnp.zeros((), g.dtype))
    elif accum in ("min", "max"):
        vals, out = res[2], res[3]
        v = vals[perm]
        attained = jnp.logical_and(_bcast(valid, v.ndim), v == out[slot_c])
        # deterministic subgradient: the first attaining element of each
        # duplicate group wins ties (elementwise over trailing axes)
        pos = jnp.where(
            attained, _bcast(jnp.arange(L, dtype=jnp.int32), v.ndim),
            jnp.int32(L),
        )
        first_pos = (
            jnp.full((nzmax,) + v.shape[1:], L, jnp.int32)
            .at[slot]
            .min(pos, mode="drop")
        )
        winner = jnp.logical_and(attained, pos == first_pos[slot_c])
        g_sorted = jnp.where(winner, g_sorted, jnp.zeros((), g.dtype))
    # perm is a permutation of [0, L): the un-sort is collision-free
    g_vals = jnp.zeros(g_sorted.shape, g_sorted.dtype).at[perm].set(g_sorted)
    return (None, None, g_vals)


_scatter_vjp.defvjp(_scatter_vjp_fwd, _scatter_vjp_bwd)


def pattern_from_perm(
    rows: jax.Array,
    cols: jax.Array,
    perm: jax.Array,
    *,
    M: int,
    N: int,
    nzmax: int,
) -> SparsePattern:
    """Parts 3-4 on an already (col,row)-ordered permutation.

    Shared tail of every planning backend (jnp / fused / pallas): the
    sort strategies differ only in how ``perm`` is produced.
    """
    return pattern_from_sorted(
        rows[perm], cols[perm], perm, M=M, N=N, nzmax=nzmax
    )


def pattern_from_sorted(
    r_s: jax.Array,
    c_s: jax.Array,
    perm: jax.Array,
    *,
    M: int,
    N: int,
    nzmax: int,
) -> SparsePattern:
    """Parts 3-4 on an already-sorted key stream.

    The tail shared by :func:`pattern_from_perm` (which sorts to get
    here) and the merge path of :meth:`SparsePattern.update` (which
    *merges* to get here, never re-sorting the survivors): ``r_s``/
    ``c_s`` are the (col,row)-ordered keys and ``perm`` maps sorted
    position back to input position.
    """
    valid = r_s < M
    first = jnp.concatenate(
        [
            jnp.ones((1,), bool),
            jnp.logical_or(c_s[1:] != c_s[:-1], r_s[1:] != r_s[:-1]),
        ]
    )
    first = jnp.logical_and(first, valid)
    # everything below is phrased gather-side (searchsorted + take):
    # XLA scatter cost scales with the update count, so the old
    # L-update bincount/indices scatters were the tail's hot spots
    cum_first = jnp.cumsum(first.astype(jnp.int32)).astype(jnp.int32)
    cum0 = jnp.concatenate([jnp.zeros((1,), jnp.int32), cum_first])
    # column j's pointer = uniques strictly before its first position
    # (c_s is globally col-sorted; padding sits inside its col group
    # with first == False, so it never moves a boundary count)
    col_bnd = jnp.searchsorted(
        c_s, jnp.arange(N + 1, dtype=jnp.int32), side="left"
    )
    jcS = cum0[col_bnd].astype(jnp.int32)
    nnz = jcS[-1].astype(jnp.int32)
    irankP = cum_first - 1
    slot = jnp.where(valid, irankP, nzmax).astype(jnp.int32)
    # row of the s-th unique = r_s where cum_first first reaches s+1;
    # s >= nnz searches past the stream and take() fills the sentinel
    upos = jnp.searchsorted(
        cum_first, jnp.arange(1, nzmax + 1, dtype=jnp.int32), side="left"
    )
    indices = jnp.take(
        r_s.astype(jnp.int32), upos, mode="fill", fill_value=M
    )
    return SparsePattern(
        perm=perm.astype(jnp.int32),
        slot=slot,
        indices=indices,
        indptr=jcS,
        nnz=nnz,
        srows=r_s.astype(jnp.int32),
        scols=c_s.astype(jnp.int32),
        shape=(M, N),
    )


#: one-time nzmax-headroom fallback warning state (mirrors the
#: ``_perm_fused`` int32-overflow pattern in ``dispatch``).
_UPDATE_FALLBACK_WARNED = False


def _reset_update_fallback_warning() -> None:
    """Test hook: re-arm the one-time update-fallback warning."""
    global _UPDATE_FALLBACK_WARNED
    _UPDATE_FALLBACK_WARNED = False


def _maybe_validated(pat: "SparsePattern") -> "SparsePattern":
    """``REPRO_VALIDATE=1`` hook: check rewritten plans on the way out.

    A no-op by default; under the env flag every non-trivial return of
    :meth:`SparsePattern.update` runs the structural validators
    (:mod:`repro.sparse.analysis.invariants`) so a merge-path bug
    surfaces as a named ``InvariantViolation`` at the rewrite, not as a
    wrong fill three calls later.  Imported lazily — the analysis layer
    depends on this module.
    """
    from .analysis.invariants import maybe_validate_pattern

    return maybe_validate_pattern(pat, subject="SparsePattern.update")


@partial(jax.jit, static_argnames=("M", "N", "nzmax", "method",
                                   "merge_method"))
def _merge_sorted_streams(
    sr_a, sc_a, pa, add_rows, add_cols, L_keep, *,
    M: int, N: int, nzmax: int, method: str | None,
    merge_method: str | None,
):
    """Sort the delta, stable-merge it into the survivors, run the tail.

    Stream A (the surviving base) wins ties — exactly the order a fresh
    stable sort over the concatenated input gives, since every survivor
    precedes every delta element in input order.  Only the small delta
    binary-searches the large survivor stream (``O(L_delta log L)`` —
    the Pallas kernel direction with the survivors VMEM-resident).  The
    merged streams are then materialized **gather-side**: one
    O(L_delta) scatter marks the delta's landing positions, a cumsum
    turns the marks into per-position source indices, and three O(L)
    gathers build the merged keys/perm — no scatter ever touches the
    large stream (XLA scatter cost scales with the update count, so
    big-side scatters would cost as much as the re-sort this path
    exists to avoid).  One jit end to end, feeding the shared Parts-3/4
    tail.
    """
    with jax.named_scope("merge"):
        nA, nB = sr_a.shape[0], add_rows.shape[0]
        Lm = nA + nB
        if nB == 0:
            return pattern_from_sorted(sr_a, sc_a, pa, M=M, N=N,
                                       nzmax=nzmax)
        dperm = sorted_permutation(add_rows, add_cols, M=M, N=N,
                                   method=method)
        sr_b = add_rows[dperm]
        sc_b = add_cols[dperm]
        # delta elements land after every survivor in the concatenated
        # input order: offset their perm values past the survivors
        pb = dperm.astype(jnp.int32) + jnp.int32(L_keep)
        off_b = merge_search(sr_b, sc_b, sr_a, sc_a, side="right",
                             method=merge_method)
        pos_b = jnp.arange(nB, dtype=jnp.int32) + off_b
        occ = jnp.zeros((Lm,), jnp.int32).at[pos_b].set(1, mode="drop")
        # deltas at positions <= q
        nb_upto = jnp.cumsum(occ).astype(jnp.int32)
        q = jnp.arange(Lm, dtype=jnp.int32)
        is_b = occ == 1
        # source index into concat([A, B]) for every merged position
        g = jnp.where(is_b, nA + nb_upto - 1, q - nb_upto)
        r_m = jnp.concatenate([sr_a, sr_b])[g]
        c_m = jnp.concatenate([sc_a, sc_b])[g]
        p_m = jnp.concatenate([pa, pb])[g]
        return pattern_from_sorted(r_m, c_m, p_m, M=M, N=N, nzmax=nzmax)


def trivial_pattern(
    L: int, shape: tuple[int, int], *, nzmax: int | None = None,
    accum: str = "sum",
) -> SparsePattern:
    """All-zero (Matlab empty-matrix) plan: every input is padding.

    The valid zero-entry structure — ``indptr = zeros(N+1)``, ``nnz =
    0``, ``indices`` all sentinel — that ``fsparse([], [], [], m, n)``
    and degenerate ``M == 0`` / ``N == 0`` shapes must produce.  Built
    directly instead of running a sort backend: an empty stream has
    nothing to sort, and the Pallas planners' digit-pass cost model /
    grid shapes assume at least one real element.
    """
    M, N = int(shape[0]), int(shape[1])
    nzmax = L if nzmax is None else nzmax
    return SparsePattern(
        perm=jnp.arange(L, dtype=jnp.int32),
        slot=jnp.full((L,), nzmax, jnp.int32),
        indices=jnp.full((nzmax,), M, jnp.int32),
        indptr=jnp.zeros((N + 1,), jnp.int32),
        nnz=jnp.zeros((), jnp.int32),
        # key storage is degenerate here: every entry of a trivial plan
        # is structural padding, so ``update`` never merges against it
        # (it degrades to a plain plan) and zero keys are as good as any
        srows=jnp.zeros((L,), jnp.int32),
        scols=jnp.zeros((L,), jnp.int32),
        shape=(M, N),
        accum=accum,
    )


@partial(jax.jit, static_argnames=("shape", "nzmax", "method", "accum",
                                   "nzmax_slack"))
def plan(
    rows: jax.Array,
    cols: jax.Array,
    shape: tuple[int, int],
    *,
    nzmax: int | None = None,
    method: str | None = None,
    accum: str = "sum",
    nzmax_slack: int = 0,
) -> SparsePattern:
    """Symbolic phase: run the paper's Parts 1-4 once, capture the plan.

    ``rows``/``cols`` are zero-offset int arrays of equal length L
    (``row == shape[0]`` marks padding).  ``method`` selects the sort
    backend (``"jnp" | "fused" | "pallas" | "radix"`` — see
    ``repro.sparse.dispatch``; ``None`` resolves to the backend-aware
    production default: ``"radix"`` on TPU, ``"fused"`` off-TPU).
    ``accum`` fixes how duplicate (i, j) values combine in the numeric
    phase (see :data:`ACCUM_MODES`; structure is accum-independent).
    ``nzmax_slack`` pre-reserves growth headroom for
    :meth:`SparsePattern.update` — when ``nzmax`` is ``None`` the
    capacity becomes ``L + nzmax_slack``, so up to ``nzmax_slack`` net
    new triplets merge in place without the full re-plan fallback
    (ignored when an explicit ``nzmax`` is given).
    The result is reusable for any
    number of :meth:`SparsePattern.assemble` calls with different value
    vectors.
    """
    M, N = int(shape[0]), int(shape[1])
    L = rows.shape[0]
    nzmax = L + int(nzmax_slack) if nzmax is None else nzmax
    validate_accum(accum)
    if L == 0 or M == 0 or N == 0:
        # Matlab empty-matrix semantics: no entry can be structural
        # (an L == 0 stream has none; a zero-dim shape makes every
        # index a sentinel), so skip the sort backends entirely
        return trivial_pattern(L, (M, N), nzmax=nzmax, accum=accum)
    rows = rows.astype(jnp.int32)
    cols = cols.astype(jnp.int32)
    with jax.named_scope("plan.sort"):
        perm = sorted_permutation(rows, cols, M=M, N=N, method=method)
    with jax.named_scope("plan.compress"):
        pat = pattern_from_perm(rows, cols, perm, M=M, N=N, nzmax=nzmax)
    return pat if accum == "sum" else dataclasses.replace(pat, accum=accum)


def plan_coo(coo: COO, *, nzmax: int | None = None,
             method: str | None = None, accum: str = "sum",
             nzmax_slack: int = 0) -> SparsePattern:
    """``plan`` over a :class:`repro.core.COO` container."""
    return plan(coo.rows, coo.cols, coo.shape, nzmax=nzmax, method=method,
                accum=accum, nzmax_slack=nzmax_slack)


# ---------------------------------------------------------------------------
# Plan-time structure detection (symmetry / block alignment)
# ---------------------------------------------------------------------------
def detect_symmetry(rows, cols, shape) -> bool:
    """Pairwise structural symmetry of the (deduplicated) triplets.

    Host-side like the facade's pre-processing: one dedup of the valid
    ``col*M + row`` keys, then an O(L) mirrored-key membership check
    (structure is a *set*, so "every mirror present" is exactly
    symmetry).  ``row == M`` sentinels are ignored.
    """
    M, N = int(shape[0]), int(shape[1])
    if M != N:
        return False
    r = np.asarray(rows).astype(np.int64).ravel()
    c = np.asarray(cols).astype(np.int64).ravel()
    keep = (r >= 0) & (r < M) & (c >= 0) & (c < N)
    r, c = r[keep], c[keep]
    if r.size == 0:
        return True
    key = np.unique(c * M + r)
    mkey = (key % M) * M + key // M
    pos = np.searchsorted(key, mkey).clip(0, key.size - 1)
    return bool(np.all(key[pos] == mkey))


def pattern_symmetric(pat: SparsePattern) -> bool:
    """Symmetry of an existing plan via the resident sorted stream.

    The deduplicated structure is the ``first``-flagged subsequence of
    the already-sorted ``(scols, srows)`` stream, so each mirror
    resolves with one :func:`~repro.sparse.dispatch.merge_search`
    probe — the same O(L) machinery the delta merge uses, no re-sort.
    """
    M, N = pat.shape
    if M != N:
        return False
    first = np.asarray(pat.first)
    srows = np.asarray(pat.srows)[first]
    scols = np.asarray(pat.scols)[first]
    keep = srows < M
    srows, scols = srows[keep], scols[keep]
    if srows.size == 0:
        return True
    t_rows = jnp.asarray(srows)
    t_cols = jnp.asarray(scols)
    # probe the mirrored pairs: (row, col) swapped; present iff the
    # right/left insertion offsets differ by exactly one
    lo = merge_search(t_cols, t_rows, t_rows, t_cols, side="left")
    hi = merge_search(t_cols, t_rows, t_rows, t_cols, side="right")
    return bool(np.all(np.asarray(hi) - np.asarray(lo) == 1))


def detect_block(rows, cols, shape, *, candidates=(8, 4, 2)) -> int:
    """Largest aligned block size whose occupied blocks are fully dense.

    Returns the largest ``b`` in ``candidates`` dividing both matrix
    dimensions for which every occupied ``b x b`` block contains all
    ``b*b`` structural entries (so BSR stores no fill-in zeros), else 1.
    """
    M, N = int(shape[0]), int(shape[1])
    r = np.asarray(rows).astype(np.int64).ravel()
    c = np.asarray(cols).astype(np.int64).ravel()
    keep = (r >= 0) & (r < M) & (c >= 0) & (c < N)
    key = np.unique(c[keep] * max(M, 1) + r[keep])
    if key.size == 0:
        return 1
    rr, cc = key % max(M, 1), key // max(M, 1)
    for b in sorted(set(int(x) for x in candidates), reverse=True):
        if b <= 1 or M % b or N % b:
            continue
        bkey = (cc // b) * (M // b) + rr // b
        _, counts = np.unique(bkey, return_counts=True)
        if np.all(counts == b * b):
            return b
    return 1


# ---------------------------------------------------------------------------
# SymPattern: the halved symmetric plan (strict-upper + diagonal slots)
# ---------------------------------------------------------------------------
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SymPattern:
    """Halved assembly plan for a structurally symmetric matrix.

    Only the strict-upper triplets are planned (``upat``) and only the
    diagonal triplets get a dense scatter — so every ``assemble``
    refill streams *half* the values a full-plan refill would, and the
    resulting :class:`~repro.sparse.formats.SymCSC` feeds the fused
    both-triangles SpMV directly.

    Contract: the input stream must be pairwise value-symmetric after
    duplicate summation (FEM element matrices are — each element
    contribution is itself symmetric).  :func:`plan_symmetric` verifies
    the *structure*; value symmetry is the caller's invariant, exactly
    like Matlab's ``issymmetric`` pre-check before a symmetric solver.

    usel : int32[Lu]  input positions of strict-upper triplets
    dsel : int32[Ld]  input positions of diagonal triplets
    drow : int32[Ld]  their (equal) row == col indices
    """

    upat: SparsePattern
    usel: jax.Array
    dsel: jax.Array
    drow: jax.Array
    shape: tuple[int, int] = dataclasses.field(metadata=dict(static=True))
    L: int = dataclasses.field(default=0, metadata=dict(static=True))

    @property
    def nzmax(self) -> int:
        """Strict-upper capacity (the halved resident plan)."""
        return self.upat.nzmax

    @property
    def epoch(self) -> int:
        return self.upat.epoch

    @property
    def nnz(self):
        return self.upat.nnz

    def assemble(self, vals: jax.Array):
        """Half-stream numeric fill -> :class:`SymCSC`.

        Gathers the ``Lu`` upper values through the halved plan and
        scatter-adds the ``Ld`` diagonal values into the dense ``diag``
        (f32 accumulation per the :func:`accum_dtype` contract).
        """
        from .formats import SymCSC

        if vals.ndim != 1 or int(vals.shape[0]) != self.L:
            raise ValueError(
                f"expected a length-{self.L} value vector aligned with "
                f"the planned triplets, got shape {tuple(vals.shape)}"
            )
        dtype = fill_dtype(vals)
        v = vals.astype(dtype)
        upper = self.upat.assemble(v[self.usel])
        acc = accum_dtype(dtype)
        diag = (
            jnp.zeros((self.shape[0],), acc)
            .at[self.drow].add(v[self.dsel].astype(acc), mode="drop")
            .astype(dtype)
        )
        return SymCSC(diag=diag, data=upper.data, indices=upper.indices,
                      indptr=upper.indptr, nnz=upper.nnz, shape=self.shape)


def plan_symmetric(
    rows,
    cols,
    shape: tuple[int, int],
    *,
    nzmax: int | None = None,
    method: str | None = None,
    accum: str = "sum",
) -> SymPattern:
    """Symbolic phase for a structurally symmetric stream.

    Verifies pairwise symmetry (``ValueError`` naming the plain-CSC
    fallback otherwise), splits the stream into strict-upper and
    diagonal triplets host-side, and plans only the upper half — the
    resident plan and every refill move half the bytes.  Host-side like
    the facade (the split is data-dependent); the returned
    :class:`SymPattern` assembles under ``jit`` like any plan.
    """
    M, N = int(shape[0]), int(shape[1])
    if M != N:
        raise ValueError(
            f"plan_symmetric requires a square matrix, got {shape}; "
            "use plan() for the plain-CSC fallback"
        )
    if accum != "sum":
        raise NotImplementedError(
            f"plan_symmetric supports accum='sum' only (got {accum!r}); "
            "use plan() for the plain-CSC fallback"
        )
    r = np.asarray(rows).astype(np.int32).ravel()
    c = np.asarray(cols).astype(np.int32).ravel()
    if not detect_symmetry(r, c, shape):
        raise ValueError(
            "the (deduplicated) structure is not pairwise symmetric — "
            "some entry (i, j) lacks a mirror (j, i); use plan() for "
            "the plain-CSC fallback"
        )
    valid = (r >= 0) & (r < M) & (c >= 0) & (c < N)
    usel = np.nonzero(valid & (r < c))[0].astype(np.int32)
    dsel = np.nonzero(valid & (r == c))[0].astype(np.int32)
    upat = plan(jnp.asarray(r[usel]), jnp.asarray(c[usel]), (M, N),
                nzmax=nzmax, method=method)
    return SymPattern(upat=upat, usel=jnp.asarray(usel),
                      dsel=jnp.asarray(dsel), drow=jnp.asarray(r[dsel]),
                      shape=(M, N), L=int(r.shape[0]))
