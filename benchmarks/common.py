"""Benchmark utilities: timing + CSV emission + JSON collection.

Every bench prints ``name,us_per_call,derived`` rows (the harness
contract) and returns the same records as dicts; ``derived`` carries
the paper-analogue quantity (speedup, fraction, bytes, ...) as
``key=value|key=value`` in the CSV and as plain keys in the dict.
``benchmarks.run --json`` serializes the collected dicts.
"""
from __future__ import annotations

import time

import jax

#: every row() call of the current process, in emission order —
#: drained by ``benchmarks.run --json`` (per-bench slicing done there).
RESULTS: list[dict] = []


def time_fn(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall time per call in microseconds (blocks on results)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def time_host_fn(fn, *args, warmup: int = 1, iters: int = 5) -> float:
    for _ in range(warmup):
        fn(*args)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(*args)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def require_no_backend(what: str) -> None:
    """Refuse to start chip-needing children from a process that holds
    a JAX backend: an accelerator belongs to one process at a time, so
    such a child would fail or hang."""
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            f"{what} starts child processes that need the device, but "
            "this process has already initialized a JAX backend; run it "
            "first (benchmarks.run does) or alone with --only"
        )


def row(name: str, us: float, **derived) -> dict:
    """Emit one CSV row; return (and collect) the machine-readable dict."""
    d = "|".join(f"{k}={v}" for k, v in derived.items())
    print(f"{name},{us:.1f},{d}")
    rec = {"name": name, "us_per_call": round(float(us), 1), **derived}
    RESULTS.append(rec)
    return rec
