"""Sharded plan-once/fill-many payoff (ShardedPattern vs one-shot).

The distributed analogue of ``bench_reassemble``: for each Table 4.2
data set over a multi-device host mesh this times

  full      plan_sharded + fill every call  (what the old
            ``core.distributed.make_distributed_assemble`` did — the
            routing analysis, histogram and sorts re-run per call)
  reuse     fill only, cached ShardedPattern (O(L/p) value shuffle +
            collision-free scatter per device)

and reports the reuse speedup.  The acceptance criterion is >= 5x:
the symbolic phase carries two size-L/p sorts plus the all_to_all
routing analysis, while the cached fill is one bucket scatter, one
all_to_all on values, and one gather+scatter.

The device count must be fixed before jax initializes.  On the CPU,
``run`` re-launches itself in a CPU-only subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count`` unless the
current process already sees multiple devices; on one accelerator it
refuses (the sharded path needs at least 2 devices, and a child could
not reach the device this process holds).
"""
from __future__ import annotations

import os
import subprocess
import sys

DEVICES = 8


def _inner(scale: float, method: str) -> list[dict]:
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core.ransparse import dataset
    from repro.sparse import plan_sharded

    from .common import row, time_fn

    rows_out = []
    for k in (1, 2, 3):
        ii, jj, ss, siz = dataset(k, seed=42, scale=scale)
        rows = jnp.asarray((ii - 1).astype(np.int32))
        cols = jnp.asarray((jj - 1).astype(np.int32))
        vals = jnp.asarray(ss.astype(np.float32))
        M = N = siz
        L = len(ii)

        def full(r, c, v):
            return plan_sharded(r, c, (M, N), method=method).assemble(v)

        pat = plan_sharded(rows, cols, (M, N), method=method)

        def reuse(p, v):
            return p.assemble(v)

        t_full = time_fn(lambda: full(rows, cols, vals))
        t_reuse = time_fn(lambda: reuse(pat, vals))
        speedup = t_full / max(t_reuse, 1e-9)
        rows_out.append(row(
            f"shard_reassemble_set{k}_full", t_full,
            L=L, size=siz, devices=len(jax.devices()), method=method,
            speedup=1.0,
        ))
        rows_out.append(row(
            f"shard_reassemble_set{k}_reuse", t_reuse,
            speedup=round(speedup, 2),
        ))
    return rows_out


def run(scale: float = 0.1, method: str = "jnp", devices: int = DEVICES):
    import jax

    devs = jax.devices()
    if len(devs) > 1:
        return _inner(scale, method)
    if devs[0].platform != "cpu":
        raise RuntimeError(
            f"shard_reassemble needs at least 2 devices; found 1 "
            f"{devs[0].platform} device ({devs[0].device_kind})"
        )
    # single CPU device: re-launch on the CPU with a forced host-device
    # count (the flag must be set before jax initializes)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = (
        os.path.join(root, "src") + os.pathsep + env.get("PYTHONPATH", "")
    )
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_shard_reassemble",
         "--scale", str(scale), "--method", method],
        env=env, capture_output=True, text=True, timeout=900, cwd=root,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"sharded bench subprocess failed:\n{out.stdout}\n{out.stderr}"
        )
    lines = [ln for ln in out.stdout.splitlines()
             if ln.startswith("shard_reassemble")]
    # re-emit through common.row so the parent's --json collector and
    # return contract see the subprocess rows as structured records
    from .common import row

    def _coerce(v: str):
        for cast in (int, float):
            try:
                return cast(v)
            except ValueError:
                continue
        return v

    out_rows = []
    for ln in lines:
        name, us, derived = ln.split(",", 2)
        kv = dict(
            (p.split("=", 1)[0], _coerce(p.split("=", 1)[1]))
            for p in derived.split("|") if "=" in p
        )
        out_rows.append(row(name, float(us), **kv))
    return out_rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--method", default="jnp")
    args = ap.parse_args()
    _inner(args.scale, args.method)
