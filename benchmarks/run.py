"""Benchmark harness: one function per paper table/figure.

  python -m benchmarks.run [--scale 0.1] [--only parts] [--json out.json]
  python -m benchmarks.run --compare BENCH_pr4.json   # regression gate
  python -m benchmarks.run --roofline                 # achieved vs peak
                                                      # bandwidth columns

Prints ``name,us_per_call,derived`` CSV rows; ``--json`` additionally
writes every row as a machine-readable record (plus environment
metadata) so CI and the committed ``BENCH_*.json`` snapshots can diff
kernel regressions.

``--compare BASE.json`` gates the *plan/fill* rows (sort backends,
kernel fills, cached reassembly, grad-of-fill) against a previous
``--json`` snapshot: any gated row slower than ``base * (1 +
--compare-tolerance)`` fails the run (default ±10% — meant for
same-machine A/B runs; CI compares across machine classes and passes a
much larger tolerance to only catch complexity-class regressions).
The baseline must have been recorded at the same ``--scale``.

Mapping to the paper:
  bench_table42        Table 4.2   overall speedup vs Matlab-oracle
  bench_reassemble     §2.3 payoff: cached SparsePattern vs full assembly
  bench_shard_reassemble  §3 payoff: cached ShardedPattern vs one-shot
                       sharded assembly over a multi-device host mesh
  bench_parts          Figs 4.1-4.3 per-part load distribution, plus a
                       per-backend sort/plan/fill comparison of every
                       registered ``method=``
  bench_spgemm         beyond-paper: two-phase SpGEMM — plan-once /
                       refill-many sparse products vs a scipy oracle
  bench_serving        beyond-paper: PlanService request latency under
                       concurrent threaded load — cold vs warm (p50/p99)
                       vs persistent warm-restart
  bench_update         beyond-paper: dynamic patterns — delta update
                       (merge-by-key) vs full re-plan at 1/10/50% of L,
                       plus warm serving/SpGEMM re-validation
  bench_access_counts  Tables 2.1/3.1 memory-access complexity
  bench_stream         §4.3 STREAM bandwidth roof
  bench_moe_dispatch   §2.1 extension: assembly as MoE dispatch
  bench_spmv           §1 FEM assemble+solve cycle, plus PR-8 format
                       rows: CSC vs SymCSC (fused both-triangles) vs
                       BSR with bytes-moved / bandwidth columns
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time

#: rows the --compare gate covers: per-backend sorts (the symbolic
#: plan), kernel fills, cached reassembly and the grad-of-fill VJP —
#: the hot plan/fill paths whose regressions the snapshots exist to
#: catch.  Oracle/model rows are reported but not gated.
GATED_ROW_RE = re.compile(
    r"(_method_|_fill_|_reuse$|_grad$|_post$|_update$|_replan$|_spmv_"
    r"|_tuned_|_prior_)"
)

#: smallest baseline timing a ratio is meaningful against.  Rows are
#: recorded at 0.1 us resolution, so a tiny smoke-scale row on a fast
#: machine can legitimately round to 0.0 — dividing by it would turn
#: timer noise into a spurious REGRESSION (or, pre-floor, a
#: ZeroDivisionError).  Such rows are skipped with a warning instead
#: of gated.
COMPARE_EPS_US = 0.05


def compare_rows(results: dict, base: dict, *, scale: float,
                 tolerance: float) -> list[str]:
    """Regression check of current plan/fill rows vs a snapshot.

    Returns a list of human-readable failures (empty == gate passed);
    prints a comparison table for every gated row found in both runs.
    Baseline rows timed below :data:`COMPARE_EPS_US` are skipped with a
    warning — a ratio against a ~0 denominator gates nothing but noise.
    """
    base_scale = base.get("meta", {}).get("scale")
    if base_scale is not None and abs(base_scale - scale) > 1e-12:
        raise SystemExit(
            f"--compare: baseline was recorded at --scale {base_scale}, "
            f"this run used --scale {scale}; timings are not comparable"
        )
    base_by_name = {
        r["name"]: r for rows in base.get("results", {}).values()
        for r in rows
    }
    failures: list[str] = []
    matched = skipped = 0
    print("compare: name,base_us,new_us,ratio,verdict", file=sys.stderr)
    for rows in results.values():
        for r in rows:
            name = r["name"]
            if not GATED_ROW_RE.search(name) or name not in base_by_name:
                continue
            b_us = float(base_by_name[name]["us_per_call"])
            n_us = float(r["us_per_call"])
            if b_us < COMPARE_EPS_US:
                skipped += 1
                print(
                    f"compare: WARNING {name} skipped — baseline timing "
                    f"{b_us:.1f}us is below the {COMPARE_EPS_US}us floor "
                    "(timer resolution); re-record the baseline at a "
                    "larger --scale to gate this row",
                    file=sys.stderr,
                )
                continue
            matched += 1
            ratio = n_us / b_us
            verdict = "ok"
            if ratio > 1.0 + tolerance:
                verdict = "REGRESSION"
                failures.append(
                    f"{name}: {b_us:.1f}us -> {n_us:.1f}us "
                    f"({ratio:.2f}x > {1.0 + tolerance:.2f}x allowed)"
                )
            elif ratio < 1.0 - tolerance:
                verdict = "improved"
            print(f"compare: {name},{b_us:.1f},{n_us:.1f},{ratio:.2f},"
                  f"{verdict}", file=sys.stderr)
    if matched == 0 and skipped == 0:
        # a rename / de-registration must not silently disarm the gate
        failures.append(
            "no gated plan/fill row matched between this run and the "
            "baseline — the gate checked nothing (row names renamed, or "
            "the baseline lacks the benches this run executed)"
        )
    elif matched == 0:
        print(
            "compare: WARNING every matched row was below the timing "
            "floor — the gate checked nothing; re-record the baseline "
            "at a larger --scale",
            file=sys.stderr,
        )
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.1,
                    help="ransparse data-set scale (1.0 = paper's 2.5M)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="write collected rows + metadata as JSON")
    ap.add_argument("--compare", default=None, metavar="BASE_JSON",
                    help="gate plan/fill rows against a previous --json "
                         "snapshot recorded at the same --scale")
    ap.add_argument("--compare-tolerance", type=float, default=0.10,
                    help="allowed slowdown fraction before the gate "
                         "fails (0.10 = ±10%%)")
    ap.add_argument("--roofline", action="store_true",
                    help="annotate kernel rows carrying bandwidth_gbs "
                         "with the device's bandwidth roof (published "
                         "peak by device_kind; the CPU's measured "
                         "STREAM) and the achieved fraction")
    args = ap.parse_args()

    from . import (
        bench_access_counts,
        bench_moe_dispatch,
        bench_parts,
        bench_reassemble,
        bench_serving,
        bench_shard_reassemble,
        bench_spgemm,
        bench_spmv,
        bench_stream,
        bench_table42,
        bench_update,
        common,
    )

    # "serving" runs first: its children need the device, so it must
    # start before any bench initializes a backend in this process
    benches = {
        "serving": lambda: bench_serving.run(scale=args.scale),
        "table42": lambda: bench_table42.run(scale=args.scale),
        "parts": lambda: bench_parts.run(scale=args.scale),
        "reassemble": lambda: bench_reassemble.run(scale=args.scale),
        "shard_reassemble": lambda: bench_shard_reassemble.run(
            scale=args.scale
        ),
        "spgemm": lambda: bench_spgemm.run(scale=args.scale),
        "update": lambda: bench_update.run(scale=args.scale),
        "access_counts": lambda: bench_access_counts.run(),
        "stream": lambda: bench_stream.run(scale=args.scale),
        "moe_dispatch": lambda: bench_moe_dispatch.run(),
        "spmv": lambda: bench_spmv.run(scale=args.scale),
    }
    print("name,us_per_call,derived")
    results: dict[str, list[dict]] = {}
    failed = []
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        start = len(common.RESULTS)
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            failed.append((name, e))
            print(f"{name},-1,error={type(e).__name__}:{e}", file=sys.stderr)
        results[name] = common.RESULTS[start:]

    if args.roofline:
        from . import roofline

        roof_name, roof = roofline.bandwidth_roof()
        frac_key = roofline.FRACTION_KEY[roof_name]
        n = sum(
            roofline.annotate_roofline(rows) for rows in results.values()
        )
        print(
            f"roofline: {roof_name} {roof:.1f} GB/s, {n} kernel rows "
            "annotated",
            file=sys.stderr,
        )
        for rows in results.values():
            for r in rows:
                if frac_key in r:
                    print(
                        f"roofline: {r['name']} "
                        f"{r['bandwidth_gbs']:.2f}/{r[roof_name]:.1f} "
                        f"GB/s = {r[frac_key] * 100:.1f}% of {roof_name}",
                        file=sys.stderr,
                    )

    if args.json:
        import jax

        payload = {
            "meta": {
                "scale": args.scale,
                "backend": jax.default_backend(),
                "device_count": jax.device_count(),
                "jax_version": jax.__version__,
                "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                           time.gmtime()),
                "failed": [f"{n}: {type(e).__name__}: {e}"
                           for n, e in failed],
            },
            "results": results,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1, default=str)
            f.write("\n")
        print(f"wrote {args.json}", file=sys.stderr)

    if args.compare:
        with open(args.compare) as f:
            base = json.load(f)
        regressions = compare_rows(
            results, base, scale=args.scale,
            tolerance=args.compare_tolerance,
        )
        if regressions:
            for line in regressions:
                print(f"compare FAILED: {line}", file=sys.stderr)
            raise SystemExit(2)
        print("compare: gate passed", file=sys.stderr)

    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
