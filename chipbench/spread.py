#!/usr/bin/env python3
"""Measure a cell's run-to-run spread, as a bound is set from it.

    python3 chipbench/spread.py --workload <cell> --seconds <s> --sets 2 \
        --seeds 11 12 13 14 15 16 [--trace-seeds 21 22 23] [--out file.json]

Runs the benchmark command once per seed and set, each run a process of its
own and one after another (this process never touches JAX, so each run has
the chip to itself), then the traced runs. For each set and end-to-end
metric it prints the median and the spread: the distance between the first
and third quartiles (``statistics.quantiles(values, n=4)``) as a share of
the median. The first run, which compiles, is left out of ``setup_s``.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def one(workload: str, seed: int, seconds: float, trace: int) -> dict:
    t = time.perf_counter()
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload", workload,
                        "--seed", str(seed), "--seconds", str(seconds),
                        "--trace", str(trace)],
                       cwd=ROOT, capture_output=True, text=True, timeout=1500)
    row = {"seed": seed, "trace": trace, "rc": p.returncode,
           "process_s": time.perf_counter() - t,
           "stderr_tail": p.stderr.strip().splitlines()[-12:]}
    lines = p.stdout.strip().splitlines()
    if p.returncode == 0 and lines:
        row["result"] = json.loads(lines[-1])
    return row


def spread(values: list) -> dict:
    med = statistics.median(values)
    q1, _, q3 = statistics.quantiles(values, n=4)
    return {"median": med, "q1": q1, "q3": q3,
            "spread": (q3 - q1) / med if med else None, "n": len(values)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    runs = []
    for s in range(args.sets):
        for seed in args.seeds:
            row = one(args.workload, seed, args.seconds, 0)
            row["set"] = s
            runs.append(row)
            print(json.dumps({k: row.get(k) for k in ("set", "seed", "rc", "process_s")}
                             | {"result": row.get("result")}), flush=True)
    for seed in args.trace_seeds:
        row = one(args.workload, seed, args.seconds, 1)
        runs.append(row)
        print(json.dumps({k: row.get(k) for k in ("seed", "trace", "rc", "process_s")}
                         | {"result": row.get("result")}), flush=True)
    summary = {"workload": args.workload, "seconds": args.seconds, "sets": []}
    for s in range(args.sets):
        rows = [r for r in runs if r.get("set") == s and "result" in r]
        metrics = {}
        for name in rows[0]["result"]["metrics"] if rows else []:
            vals = [r["result"]["metrics"][name]["value"] for r in rows
                    if not (name == "setup_s" and r is runs[0])]
            if len(vals) >= 2:
                metrics[name] = spread(vals)
        summary["sets"].append({"set": s, "correct": [r["result"]["correct"] for r in rows],
                                "metrics": metrics})
    summary["failed_runs"] = [{k: r.get(k) for k in ("set", "seed", "trace", "rc",
                                                    "stderr_tail")}
                              for r in runs if r["rc"] != 0 or not
                              r.get("result", {}).get("correct", False)]
    print(json.dumps(summary), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"summary": summary, "runs": runs}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
