"""Benchmark of the sparse assembly service on a TPU.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` is a ``workloads`` entry of ``BENCHMARK.json``.  The run makes
its inputs from ``--seed``, sets up and warms the cell, measures a
closed loop for ``--seconds`` seconds, checks the sampled results
against the plain reference, and prints one JSON line last on standard
output.  It exits non-zero, with no result line, when JAX finds no TPU
or fewer chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the bfloat16 reference in the program's "
                         "place (the check must then fail)")
    args = ap.parse_args(argv)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench import harness

    return harness.run(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace), control=args.control)


if __name__ == "__main__":
    sys.exit(main())
