#!/usr/bin/env python3
"""The control of the check: the plain reference with 3-bit sketch counters
(saturating at 7, where the configurations state 4-bit counters saturating
at 15), put in the program's place. The check has to refuse it.

    python3 chipbench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed: one run of the cell at its own size and load (set-up,
warm-up, a window of ``--seconds``), then the check twice: of what the
program produced (the lower readings) and of what the control produces over
the same accesses (the upper readings). Prints one JSON line per seed. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

#: the reference parameters the control changes
CONTROL = {"sketch.cap": 7}


def control_outcome(harness, m):
    """What the control produces over the accesses the run drove, in the
    program's place."""
    refcfg = json.loads(json.dumps(m.cell.config["reference"]))
    refcfg["sketch"]["cap"] = CONTROL["sketch.cap"]
    ref = harness.load_module(m.cell.file("reference", f"{refcfg['module']}.py"),
                              f"chipbench_reference_{refcfg['module']}")
    part = (ref.shard_of(m.keys, m.sizing.shards, refcfg.get("shard_salt", 0))
            if m.sizing.shards > 1 else None)
    caches = []
    for k, c in enumerate(m.out.caches):
        cache = ref.Cache(refcfg, m.sizing.capacity, m.sizing.entries)
        hits = cache.run(c["keys"], c["sizes"])
        st = cache.state()
        caches.append({"keys": c["keys"], "sizes": c["sizes"], "hits": hits,
                       "window_from": c["window_from"],
                       "routed": None if part is None else m.keys[part == k],
                       "state": st})
    return harness.Outcome(caches, {})


def readings(harness, workload: str, seed: int, seconds: float, **kw) -> dict:
    m = harness.measure(workload, seed, seconds, False, **kw)
    lower, _ = harness.check(m.cell, m.out, m.keys, m.sizing)
    upper, _ = harness.check(m.cell, control_outcome(harness, m), m.keys, m.sizing)
    return {"workload": workload, "seed": seed, "device": m.device,
            "accesses": sum(len(c["keys"]) for c in m.out.caches),
            "window_accesses": m.win.accesses, "program": lower, "control": upper}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--scale", type=float, default=1.0)
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import harness

    for seed in args.seeds:
        try:
            row = readings(harness, args.workload, seed, args.seconds,
                           rehearse=args.rehearse, scale=args.scale)
        except harness.HarnessError as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
