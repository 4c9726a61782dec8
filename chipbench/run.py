#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last stdout line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs on the chip the cell asks for (``BENCHMARK.json``) and fails, printing
no result, where JAX finds no TPU or too few chips. ``--trace 0`` reports
the cell's end-to-end metrics; ``--trace 1`` its per-layer metrics, read
from a profiler trace of part of the window. The last lines on standard
error, and the ``checks`` key of the result, give each number the check
against the plain reference compared, beside its limit.

``--rehearse`` is a CPU rehearsal for whoever changes the harness: the
program on XLA-CPU with its jnp sketch, ``--scale`` shrinking the traffic;
it reports no metric.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: no chip, no metric")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="rehearsal only: shrink the traffic class")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw profiler trace to this path")
    args = ap.parse_args(argv)
    if args.scale != 1.0 and not args.rehearse:
        ap.error("--scale is for rehearsals only")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import harness

    try:
        result = harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                             rehearse=args.rehearse, scale=args.scale,
                             keep_trace=args.keep_trace)
    except harness.HarnessError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
