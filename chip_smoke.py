"""Smoke run of the served assembly path on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded path on four chips

One chip: Table 4.1 sets 1-3 at the paper's size (L = 2.5e6 triplets
each), then set 2 at 16x (L = 4e7, M = N = 8e5).  Each size goes
through one ``PlanService``: a cold ``assemble`` (which plans with the
resolved method, the compiled Pallas radix planner on TPU), 3 warm
``assemble`` calls, an ``assemble_many`` of 2, a CSC ``spmv``, an
``update_structure`` with a 1% delta (1% of the triplets dropped, as
many new ones added), on set 3 a SymCSC ``spmv`` of the symmetrized
stream, and on set 1 ``multiply(A, A)``.

``--chips 4``: ``plan_sharded`` over a 4-device data mesh on set 2 at
16x (1e7 triplets per chip), then ``.assemble`` and ``ShardedCSC @ x``.

Every result is checked against an independent reference: the NumPy
Matlab oracle (``matlab_sparse_oracle``) bit for bit, a fresh
``fsparse`` over the concatenated triplets for the update, a float64
NumPy matvec for spmv and scipy for the product.  Values and vectors
are integer-valued floats drawn from the seed, so every sum is exact in
float32 and the comparisons are exact.

Each phase prints its seconds on a line of its own; they include
compilation and transfers and are not metrics.  The last line is one
JSON object naming the device.  The script exits non-zero without that
line when JAX finds no TPU or any check fails.  JAX's compile cache goes
where ``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache`` in
the checkout.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

#: paper-size sets (scale 1.0) and the deployment-size run (set 2, 16x)
PAPER_SETS = (1, 2, 3)
DEPLOY_SET, DEPLOY_SCALE = 2, 16.0


def _phase(log, name: str, fn):
    """Run one phase, block on its result, print its seconds."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    log(f"phase {name} {time.perf_counter() - t0:.3f}s")
    return out


def triplets(k: int, scale: float, seed: int):
    """Table 4.1 set ``k`` with integer-valued values in [-4, 4]."""
    from repro.core.ransparse import dataset

    ii, jj, _, siz = dataset(k, seed=seed, scale=scale)
    rng = np.random.default_rng(seed + k)
    ss = rng.integers(-4, 5, size=ii.shape).astype(np.float64)
    return ii, jj, ss, int(siz)


class Oracle:
    """``matlab_sparse_oracle`` of one stream, for exact comparisons."""

    def __init__(self, ii, jj, ss, M: int, N: int):
        from repro.core.oracle import matlab_sparse_oracle

        self.M, self.N = M, N
        self.pr, self.ir, self.jc = matlab_sparse_oracle(
            np.asarray(ii) - 1, np.asarray(jj) - 1, ss, M, N
        )

    def check(self, what: str, A, scale: float = 1.0) -> None:
        """``A`` equals the oracle of ``scale * values``, bit for bit
        (integer values: the scaled oracle is exact)."""
        nnz = int(A.nnz)
        if nnz != len(self.pr):
            raise AssertionError(f"{what}: nnz {nnz} != oracle {len(self.pr)}")
        np.testing.assert_array_equal(np.asarray(A.indptr), self.jc,
                                      err_msg=f"{what}: indptr")
        np.testing.assert_array_equal(np.asarray(A.indices)[:nnz], self.ir,
                                      err_msg=f"{what}: indices")
        np.testing.assert_array_equal(
            np.asarray(A.data)[:nnz].astype(np.float64), self.pr * scale,
            err_msg=f"{what}: data")

    def matvec(self, x) -> np.ndarray:
        cols = np.repeat(np.arange(self.N), np.diff(self.jc))
        return np.bincount(self.ir, weights=self.pr * x[cols],
                           minlength=self.M)

    def scipy(self):
        import scipy.sparse as sp

        return sp.csc_matrix((self.pr, self.ir, self.jc),
                             shape=(self.M, self.N))


def _check_matvec(what: str, y, want) -> None:
    np.testing.assert_allclose(np.asarray(y, np.float64), want, rtol=1e-5,
                               atol=0, err_msg=what)


def _vector(n: int, seed: int):
    rng = np.random.default_rng(seed)
    return rng.integers(-3, 4, size=n).astype(np.float32)


def single_chip_size(sets, scale: float, *, seed: int = 42, log=print):
    """All one-chip phases for ``sets`` at ``scale``, one PlanService."""
    import jax
    import jax.numpy as jnp

    from repro.sparse import PlanService, convert, fsparse, plan

    svc = PlanService()
    tag = f"scale={scale:g}"
    for k in sets:
        ii, jj, ss, n = triplets(k, scale, seed)
        L = len(ii)
        shape = (n, n)
        where = f"{tag}/set{k}"
        log(f"{where}: L={L} M=N={n}")
        ref = _phase(log, f"{where}/oracle",
                     lambda: Oracle(ii, jj, ss, n, n))

        A = _phase(log, f"{where}/assemble_cold",
                   lambda: svc.assemble(ii, jj, ss, shape))
        ref.check(f"{where}/assemble_cold", A)
        planned = svc.stats()["plan"]["misses"]
        # the planning program as method=None resolves it: on TPU the
        # Pallas kernels must be compiled in (no interpret-mode fallback)
        hlo = plan.lower(jnp.asarray(ii - 1, jnp.int32),
                         jnp.asarray(jj - 1, jnp.int32), shape).as_text()
        kernels = "tpu_custom_call" in hlo
        log(f"{where}: plan program has tpu_custom_call={kernels}")
        if jax.default_backend() == "tpu" and not kernels:
            raise AssertionError(f"{where}: planning ran no Pallas kernel")

        for c in (2, 3, 4):
            W = _phase(log, f"{where}/assemble_warm{c - 1}",
                       lambda c=c: svc.assemble(ii, jj, ss * c, shape))
            ref.check(f"{where}/assemble_warm{c - 1}", W, scale=c)
        many = _phase(log, f"{where}/assemble_many2", lambda: svc.assemble_many(
            [(ii, jj, ss * 5, shape), (ii, jj, ss * 6, shape)]))
        if svc.stats()["plan"]["misses"] != planned:
            raise AssertionError(f"{where}: a warm request re-planned")
        for c, B in zip((5, 6), many):
            ref.check(f"{where}/assemble_many2", B, scale=c)

        x = _vector(n, seed + 100 + k)
        y = _phase(log, f"{where}/spmv_csc", lambda: svc.spmv(A, x))
        _check_matvec(f"{where}/spmv_csc", y, ref.matvec(x.astype(np.float64)))

        # churn: drop 1% of the triplets and add as many new ones, so
        # the merged stream fits the plan's nzmax and merges in place
        rng = np.random.default_rng(seed + 200 + k)
        Ld = L // 100
        drop = np.zeros(L, bool)
        drop[rng.choice(L, Ld, replace=False)] = True
        ai = rng.integers(1, n + 1, Ld)
        aj = rng.integers(1, n + 1, Ld)
        av = rng.integers(-4, 5, Ld).astype(np.float64)
        U = _phase(log, f"{where}/update_structure_1pct",
                   lambda: svc.update_structure(ii, jj, ss, ai, aj, av,
                                                shape, drop_mask=drop))
        keep = ~drop
        F = _phase(log, f"{where}/update_reference_fsparse",
                   lambda: fsparse(np.concatenate([ii[keep], ai]),
                                   np.concatenate([jj[keep], aj]),
                                   np.concatenate([ss[keep], av]), shape))
        for field in ("nnz", "indptr", "indices", "data"):
            np.testing.assert_array_equal(
                np.asarray(getattr(U, field)), np.asarray(getattr(F, field)),
                err_msg=f"{where}/update_structure: {field}")
        del U, F

        if k == 3:
            si, sj = np.concatenate([ii, jj]), np.concatenate([jj, ii])
            sv = np.concatenate([ss, ss])
            sref = _phase(log, f"{where}/sym_oracle",
                          lambda: Oracle(si, sj, sv, n, n))
            As = _phase(log, f"{where}/assemble_symmetrized",
                        lambda: svc.assemble(si, sj, sv, shape))
            sref.check(f"{where}/assemble_symmetrized", As)
            S = convert(As, "symcsc")
            ys = _phase(log, f"{where}/spmv_symcsc", lambda: svc.spmv(S, x))
            _check_matvec(f"{where}/spmv_symcsc", ys,
                          sref.matvec(x.astype(np.float64)))
            del As, S

        if k == 1:
            C = _phase(log, f"{where}/multiply_AxA",
                       lambda: svc.multiply(A, A))
            want = _phase(log, f"{where}/multiply_scipy",
                          lambda: ref.scipy() @ ref.scipy())
            _check_product(f"{where}/multiply_AxA", C, want)
        del A, W, many


def _check_product(what: str, C, want) -> None:
    import scipy.sparse as sp

    nnz = int(C.nnz)
    got = sp.csc_matrix(
        (np.asarray(C.data)[:nnz].astype(np.float64),
         np.asarray(C.indices)[:nnz], np.asarray(C.indptr)),
        shape=C.shape,
    )
    diff = abs(got - want)
    if diff.nnz and diff.max() != 0:
        raise AssertionError(f"{what}: differs from scipy by {diff.max()}")


def sharded_phase(scale: float, n_devices: int, *, k: int = DEPLOY_SET,
                  seed: int = 42, log=print):
    """``plan_sharded`` over an ``n_devices`` data mesh, ``.assemble``
    and ``ShardedCSC @ x``, against one-device ``fsparse`` and the
    oracle."""
    import jax.numpy as jnp

    from repro.core.csc import spmv as csc_spmv
    from repro.launch.mesh import make_data_mesh
    from repro.sparse import fsparse, plan_sharded

    mesh = make_data_mesh(n_devices)
    ii, jj, ss, n = triplets(k, scale, seed)
    where = f"sharded{n_devices}/scale={scale:g}/set{k}"
    log(f"{where}: L={len(ii)} M=N={n} devices={n_devices}")
    ref = _phase(log, f"{where}/oracle", lambda: Oracle(ii, jj, ss, n, n))
    rows = jnp.asarray(ii - 1, jnp.int32)
    cols = jnp.asarray(jj - 1, jnp.int32)
    vals = jnp.asarray(ss, jnp.float32)
    pat = _phase(log, f"{where}/plan_sharded",
                 lambda: plan_sharded(rows, cols, (n, n), mesh=mesh))
    if bool(pat.any_overflow()):
        raise AssertionError(f"{where}: all_to_all bucket overflow")
    S = _phase(log, f"{where}/assemble", lambda: pat.assemble(vals))
    used = {d for a in (S.data, S.indices) for d in a.sharding.device_set}
    if len(used) != n_devices:
        raise AssertionError(f"{where}: result lives on {len(used)} devices")
    one = _phase(log, f"{where}/fsparse_one_device",
                 lambda: fsparse(ii, jj, ss, (n, n)))
    ref.check(f"{where}/fsparse_one_device", one)
    ref.check(f"{where}/assemble", _gather_blocks(S))
    x = _vector(n, seed + 300)
    y = _phase(log, f"{where}/spmv", lambda: S @ jnp.asarray(x))
    _check_matvec(f"{where}/spmv", y, ref.matvec(x.astype(np.float64)))
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(csc_spmv(one, jnp.asarray(x))),
                                  err_msg=f"{where}/spmv_vs_one_device")


def _gather_blocks(S):
    """A row-block ShardedCSC as one global CSC on the host."""
    from repro.core.csc import CSC

    data, idx = np.asarray(S.data), np.asarray(S.indices)
    ptr, nnz = np.asarray(S.indptr), np.asarray(S.nnz)
    rpb, (M, N) = S.rows_per_block, S.shape
    rows, cols, vals = [], [], []
    for b in range(S.n_blocks):
        nb = int(nnz[b])
        rows.append(idx[b, :nb].astype(np.int64) + b * rpb)
        cols.append(np.repeat(np.arange(N), np.diff(ptr[b])))
        vals.append(data[b, :nb])
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    order = np.argsort(cols, kind="stable")   # blocks are row-ascending
    indptr = np.concatenate([[0], np.cumsum(np.bincount(cols, minlength=N))])
    return CSC(data=np.concatenate(vals)[order], indices=rows[order],
               indptr=indptr, nnz=len(rows), shape=(M, N))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the PlanService phases; 4: the sharded path")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2

    from repro.sparse import dispatch, tuning
    from repro.sparse.serving import enable_compilation_cache

    log = lambda msg: print(msg, flush=True)  # noqa: E731
    log(f"jax {jax.__version__}: {len(devices)} x {dev.device_kind}")
    log(f"compile cache: {enable_compilation_cache()}")
    log(f"policy plan.method={dispatch.default_method()} "
        f"merge.method={dispatch.default_merge_method()} "
        f"spmv_sym.method={tuning.resolve_policy('spmv_sym')['method']}")
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(DEPLOY_SCALE, 4, log=log)
    else:
        single_chip_size(PAPER_SETS, 1.0, log=log)
        single_chip_size((DEPLOY_SET,), DEPLOY_SCALE, log=log)
    log(f"total {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
