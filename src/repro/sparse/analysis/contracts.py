"""Jaxpr contract auditor: static dtype/host-transfer/retrace checks.

The fill, SpGEMM and SpMV hot paths promise three things that are easy
to break silently and expensive to discover at runtime:

* the :func:`repro.sparse.pattern.fill_dtype` /
  :func:`~repro.sparse.pattern.accum_dtype` contract — duplicate
  accumulation never runs in a 16-bit float (bf16/f16 streams promote
  to f32 for the reduction, outputs demote once at the end);
* no host callbacks or infeed/outfeed primitives inside a jitted hot
  path (one stray ``debug_callback`` serializes every request);
* retrace accounting — a structure ``epoch`` bump retraces exactly
  once, a value-only change retraces zero times.

:func:`audit_jaxpr` checks the first two statically on any traced
jaxpr (recursing into scan/cond/pjit/custom_vjp sub-jaxprs);
:func:`audit_default_paths` traces every registered fill/multiply/spmv
path over small representative structures and audits each;
:class:`RetraceAuditor` is the reusable retrace counter (promoted from
the ad-hoc ``traces = []`` lists the update tests grew), and
:func:`audit_retraces` is its self-contained epoch-bump check.

SpMV paths are audited at f32: the dot-product accumulation dtype of
``matmul`` follows the operand dtype (dense-matmul semantics), so a
bf16 SpMV legitimately adds in bf16 — only the *fill* paths own the
f32-accumulation contract.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..errors import InvariantViolation

__all__ = [
    "RetraceAuditor",
    "audit_default_paths",
    "audit_jaxpr",
    "audit_retraces",
    "iter_eqns",
]

#: primitives that *sum* their operand — where 16-bit accumulation
#: compounds rounding error over duplicate chains.  min/max/first/last
#: scatters are exact selections and are deliberately not listed.
_SUM_PRIMITIVES = frozenset(
    {
        "add_any",
        "cumsum",
        "reduce_sum",
        "reduce_window_sum",
        "scatter-add",
    }
)
_HOST_PRIMITIVES = frozenset({"infeed", "outfeed", "debug_print"})
_16BIT_FLOATS = ("bfloat16", "float16")


def _subjaxprs(value):
    """Yield the jaxprs stashed in one equation-param value."""
    vals = value if isinstance(value, (tuple, list)) else (value,)
    for v in vals:
        inner = getattr(v, "jaxpr", v)  # ClosedJaxpr -> Jaxpr
        if hasattr(inner, "eqns") and hasattr(inner, "invars"):
            yield inner


def iter_eqns(jaxpr):
    """Depth-first over every equation, including the sub-jaxprs of
    scan/while/cond/pjit/custom_vjp bodies hiding in equation params."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from iter_eqns(sub)


def audit_jaxpr(
    traced,
    *,
    name: str = "jaxpr",
    expect_dtype=None,
    forbid_16bit_accum: bool = True,
    forbid_callbacks: bool = True,
) -> dict:
    """Statically audit one traced computation.

    ``traced`` is a ``ClosedJaxpr`` (what :func:`jax.make_jaxpr`
    returns) or a bare ``Jaxpr``.  Raises
    :class:`~repro.sparse.errors.InvariantViolation` named

    * ``16-bit-accumulation`` — a summing primitive consumes a
      bf16/f16 operand (the ``accum_dtype`` contract requires f32);
    * ``host-callback`` — a callback/infeed/outfeed primitive lowers
      inside the hot path;
    * ``output-dtype`` — a floating output's dtype differs from
      ``expect_dtype`` (the ``fill_dtype`` contract), when given.

    Returns a small report dict (name, equation count, primitive set)
    on success.
    """
    jaxpr = getattr(traced, "jaxpr", traced)
    n_eqns = 0
    prims: set[str] = set()
    for eqn in iter_eqns(jaxpr):
        n_eqns += 1
        pname = eqn.primitive.name
        prims.add(pname)
        if forbid_callbacks and (
            "callback" in pname or pname in _HOST_PRIMITIVES
        ):
            raise InvariantViolation(
                "host-callback",
                f"hot path lowers the host primitive {pname!r}",
                subject=name,
            )
        if forbid_16bit_accum and pname in _SUM_PRIMITIVES:
            for var in eqn.invars:
                dt = getattr(getattr(var, "aval", None), "dtype", None)
                if dt is not None and str(dt) in _16BIT_FLOATS:
                    raise InvariantViolation(
                        "16-bit-accumulation",
                        f"{pname} accumulates {dt} operands; the "
                        "accum_dtype contract requires an f32 "
                        "accumulator for 16-bit streams",
                        subject=name,
                    )
    if expect_dtype is not None:
        want = jnp.dtype(expect_dtype)
        out_avals = getattr(traced, "out_avals", ())
        bad = sorted(
            {
                str(a.dtype)
                for a in out_avals
                if jnp.issubdtype(a.dtype, jnp.floating) and a.dtype != want
            }
        )
        if bad:
            raise InvariantViolation(
                "output-dtype",
                f"floating outputs {bad} do not match the fill_dtype "
                f"contract ({want})",
                subject=name,
            )
    return {
        "name": name,
        "eqns": n_eqns,
        "primitives": sorted(prims),
        "ok": True,
    }


def _representative_structures():
    """Small operands exercising every registered hot path."""
    from ..formats import convert
    from ..pattern import plan

    # 4x4, duplicates in (2,2), structurally + numerically symmetric
    rows = np.array([0, 1, 0, 2, 2, 2, 3], np.int64)
    cols = np.array([0, 0, 1, 2, 2, 3, 2], np.int64)
    pat = plan(rows, cols, (4, 4))
    A = pat.assemble(jnp.ones((rows.size,), jnp.float32))
    return pat, A, convert(A, "symcsc"), convert(A, "bsr", block=2)


def audit_default_paths(*, dtypes=(jnp.float32, jnp.bfloat16)) -> list[dict]:
    """Trace and audit every registered fill/multiply/spmv path.

    Fills and SpGEMM multiplies run per ``accum`` mode and per dtype
    in ``dtypes`` (bf16 included by default — that is where a missing
    f32 promotion shows up as a ``scatter-add``/``cumsum`` over bf16);
    SpMV paths run at f32 (see module docstring).  Returns the list of
    per-path report dicts; raises ``InvariantViolation`` on the first
    broken contract.
    """
    from .. import ops as sparse_ops
    from ..pattern import ACCUM_MODES, fill_dtype
    from ..spgemm import product_plan

    pat, A, Y, B2 = _representative_structures()
    reports: list[dict] = []

    def _audit(fn, args, *, name, expect=None):
        closed = jax.make_jaxpr(fn)(*args)
        reports.append(audit_jaxpr(closed, name=name, expect_dtype=expect))

    for accum in ACCUM_MODES:
        for dtype in dtypes:
            dt = jnp.dtype(dtype)
            vals = jnp.ones((pat.L,), dt)
            _audit(
                lambda v, a=accum: pat.scatter(v, accum=a),
                (vals,),
                name=f"fill[{accum},{dt.name}]",
                expect=fill_dtype(dt),
            )

    pp = product_plan(A, A)
    for dtype in dtypes:
        dt = jnp.dtype(dtype)
        da = jnp.ones((pp.a_capacity,), dt)
        db = jnp.ones((pp.b_capacity,), dt)
        _audit(
            lambda a, b: pp.multiply(a, b).data,
            (da, db),
            name=f"spgemm[{dt.name}]",
            expect=fill_dtype(dt),
        )

    x = jnp.ones((4,), jnp.float32)
    for mat, label in ((A, "csc"), (Y, "symcsc"), (B2, "bsr")):
        _audit(
            lambda m, v: sparse_ops.matmul(m, v),
            (mat, x),
            name=f"spmv[{label},float32]",
            expect=jnp.float32,
        )
    return reports


class RetraceAuditor:
    """Counts how often a jitted callable actually retraces.

    ``instrument(fn)`` returns ``jax.jit`` of ``fn`` with a trace-time
    side channel: every *trace* (not every call) appends to the log, so
    ``count`` is the retrace total.  ``expect(n)`` turns a mismatch
    into a named ``InvariantViolation("retrace-count")`` — the
    mechanical form of the epoch contract: structure bump => exactly
    one retrace, value-only change => zero.
    """

    def __init__(self) -> None:
        self._log: list[str] = []

    @property
    def count(self) -> int:
        return len(self._log)

    def reset(self) -> None:
        self._log.clear()

    def instrument(self, fn, **jit_kwargs):
        name = getattr(fn, "__name__", "<fn>")

        def _traced(*args, **kwargs):
            self._log.append(name)
            return fn(*args, **kwargs)

        return jax.jit(_traced, **jit_kwargs)

    def expect(self, n: int, *, what: str = "jitted path") -> int:
        if self.count != n:
            raise InvariantViolation(
                "retrace-count",
                f"expected exactly {n} trace(s), observed {self.count} "
                f"({self._log})",
                subject=what,
            )
        return self.count


def audit_retraces() -> dict:
    """Self-contained epoch retrace check over a tiny pattern.

    Value-only changes replay the compiled fill (zero retraces); an
    ``epoch`` bump with identical shapes retraces exactly once.
    """
    from ..pattern import plan

    auditor = RetraceAuditor()
    fill = auditor.instrument(lambda p, v: p.scatter(v))
    pat = plan(np.array([0, 1, 1]), np.array([0, 0, 1]), (2, 2))
    vals = jnp.ones((pat.L,), jnp.float32)
    fill(pat, vals)
    auditor.expect(1, what="fill after first call")
    fill(pat, 2.0 * vals)
    auditor.expect(1, what="fill after a value-only change")
    bumped = dataclasses.replace(pat, epoch=pat.epoch + 1)
    fill(bumped, vals)
    auditor.expect(2, what="fill after an epoch bump")
    return {"name": "retrace", "traces": auditor.count, "ok": True}
