"""Serving-path latency under concurrent load (PlanService).

For each Table 4.2 data set this measures the three request regimes a
plan server sees:

  cold      first request in a fresh process with an empty cache dir —
            pays the symbolic plan, the jit trace and the XLA compile
  warm      steady state: ``threads`` threads hammer the service with
            ``requests`` fills each; per-request wall latency is
            collected and reported as p50 (gated) / p99 (derived)
  restart   first request in a *second* fresh process pointed at the
            same cache dir — the plan replays from disk and the
            executable comes out of the persistent compilation cache,
            so neither the symbolic phase nor the XLA compile re-runs

and reports ``speedup_vs_cold`` on the restart rows (the warm-restart
acceptance criterion is >= 2x).  Every phase asserts the serving path
is bit-identical to uncached ``fsparse`` dispatch before timing.

Cache state (plan caches, the persistent compilation cache config) is
process-global, so both phases run as fresh subprocesses of ``run``;
rows are re-emitted in the parent for the ``--json`` collector.  The
parent never touches a JAX backend (each child in turn holds the
device), and both children share JAX's compile cache
(``JAX_COMPILATION_CACHE_DIR``, else the checkout's fixed
``.jax_cache``) — so "cold" means cold plans, not necessarily cold
compiles.  The plan pickles go to the fixed ``.bench_cache/serving``,
emptied before the cold phase.
"""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

THREADS = 4
REQUESTS = 8


def _inner(phase: str, scale: float, cache_dir: str,
           threads: int, requests: int) -> list[dict]:
    import threading
    import time

    import numpy as np
    import jax

    from repro.core.ransparse import dataset
    from repro.sparse import PlanService, fsparse, plan_cache_info

    from .common import row

    svc = PlanService(cache_dir=cache_dir)
    if phase == "restart":
        assert svc.loaded_plans >= 1, (
            f"restart phase found no persisted plans in {cache_dir}")

    rows_out = []
    for k in (1, 2, 3):
        ii, jj, ss, siz = dataset(k, seed=42, scale=scale)
        L = len(ii)

        t0 = time.perf_counter()
        A = svc.assemble(ii, jj, ss, (siz, siz))
        jax.block_until_ready(A.data)
        first_us = (time.perf_counter() - t0) * 1e6

        # serving path must be bit-identical to uncached dispatch
        ref = fsparse(ii, jj, ss, (siz, siz))
        np.testing.assert_array_equal(np.asarray(A.indptr),
                                      np.asarray(ref.indptr))
        np.testing.assert_array_equal(np.asarray(A.indices),
                                      np.asarray(ref.indices))
        np.testing.assert_array_equal(np.asarray(A.data),
                                      np.asarray(ref.data))

        # steady state: T threads x R requests against the warm service
        lat: list[float] = []
        lock = threading.Lock()
        barrier = threading.Barrier(threads)

        def worker():
            local = []
            barrier.wait()
            for _ in range(requests):
                t1 = time.perf_counter()
                out = svc.assemble(ii, jj, ss, (siz, siz))
                jax.block_until_ready(out.data)
                local.append(time.perf_counter() - t1)
            with lock:
                lat.extend(local)

        ts = [threading.Thread(target=worker) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        lat.sort()
        n = len(lat)
        p50_us = lat[n // 2] * 1e6
        p99_us = lat[min(n - 1, int(n * 0.99))] * 1e6

        if phase == "cold":
            rows_out.append(row(
                f"serving_set{k}_cold", first_us,
                L=L, size=siz, threads=threads,
            ))
            rows_out.append(row(
                f"serving_set{k}_warm_fill_p50", p50_us,
                p99_us=round(p99_us, 1), requests=n,
            ))
        else:
            rows_out.append(row(
                f"serving_set{k}_restart", first_us,
                loaded_plans=svc.loaded_plans,
            ))
            rows_out.append(row(
                f"serving_set{k}_restart_fill_p50", p50_us,
                p99_us=round(p99_us, 1), requests=n,
            ))

    if phase == "restart":
        # the whole point of the restart: every plan replayed from disk
        info = plan_cache_info()
        assert info["misses"] == 0, (
            f"warm restart re-planned: {info['misses']} plan-cache misses")
    return rows_out


_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: fixed plan-pickle directory shared by the cold and restart children
PLAN_DIR = os.path.join(_ROOT, ".bench_cache", "serving")


def _launch(phase: str, scale: float, cache_dir: str,
            threads: int, requests: int) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        os.path.join(_ROOT, "src") + os.pathsep + env.get("PYTHONPATH", "")
    )
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_serving",
         "--phase", phase, "--scale", str(scale), "--cache-dir", cache_dir,
         "--threads", str(threads), "--requests", str(requests)],
        env=env, capture_output=True, text=True, timeout=900, cwd=_ROOT,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"serving bench {phase} subprocess failed:\n"
            f"{out.stdout}\n{out.stderr}"
        )
    return out.stdout


def run(scale: float = 0.1, threads: int = THREADS,
        requests: int = REQUESTS):
    from .common import require_no_backend, row

    require_no_backend("bench_serving")

    def _coerce(v: str):
        for cast in (int, float):
            try:
                return cast(v)
            except ValueError:
                continue
        return v

    def _parse(stdout: str) -> list[tuple[str, float, dict]]:
        parsed = []
        for ln in stdout.splitlines():
            if not ln.startswith("serving_"):
                continue
            name, us, derived = ln.split(",", 2)
            kv = dict(
                (p.split("=", 1)[0], _coerce(p.split("=", 1)[1]))
                for p in derived.split("|") if "=" in p
            )
            parsed.append((name, float(us), kv))
        return parsed

    shutil.rmtree(PLAN_DIR, ignore_errors=True)
    cold = _parse(_launch("cold", scale, PLAN_DIR, threads, requests))
    restart = _parse(_launch("restart", scale, PLAN_DIR, threads, requests))

    cold_us = {name: us for name, us, _ in cold}
    out_rows = []
    for name, us, kv in cold:
        out_rows.append(row(name, us, **kv))
    for name, us, kv in restart:
        if name.endswith("_restart"):
            ref = cold_us.get(name.replace("_restart", "_cold"))
            if ref:
                kv["speedup_vs_cold"] = round(ref / max(us, 1e-9), 2)
        out_rows.append(row(name, us, **kv))
    return out_rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--phase", required=True, choices=["cold", "restart"])
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--cache-dir", required=True)
    ap.add_argument("--threads", type=int, default=THREADS)
    ap.add_argument("--requests", type=int, default=REQUESTS)
    args = ap.parse_args()
    _inner(args.phase, args.scale, args.cache_dir,
           args.threads, args.requests)
