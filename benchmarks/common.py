"""Shared benchmark plumbing.

Every benchmark emits rows ``name,us_per_call,derived`` (CSV) and dumps full
JSON to ``benchmarks/results/<module>.json`` for EXPERIMENTS.md.

Scale control: ``REPRO_BENCH_SCALE`` (default 0.08) shrinks trace lengths;
1.0 reproduces the paper-scaled traces of ``repro.traces.TRACE_SPECS``.
"""

from __future__ import annotations

import json
import os
import pathlib
import time

from repro.core import REGISTRY, PolicySpec, SimulationEngine
from repro.traces import make_trace

RESULTS_DIR = pathlib.Path(__file__).parent / "results"

PAPER_TRACES = ("msr2", "systor2", "tencent1", "cdn1")
# Cache sizes as fractions of total unique bytes; the two largest model the
# paper's "practically unbounded" 1TB/10TB points (AdaptSize pathology, §5.2).
CACHE_FRACS = (0.001, 0.01, 0.1, 0.5)


def bench_scale() -> float:
    return float(os.environ.get("REPRO_BENCH_SCALE", "0.08"))


def sequential_mode() -> bool:
    """``--sequential`` escape hatch: drive sweeps as per-policy loops
    instead of the fleet path."""
    return bool(os.environ.get("REPRO_BENCH_SEQUENTIAL"))


def get_trace(name: str, seed: int = 0):
    return make_trace(name, seed=seed, scale=bench_scale())


def run_policy(name: "str | PolicySpec", trace, cap: int, *, engine: SimulationEngine | None = None,
               with_snapshots: bool = False, limit: "int | None" = None, **kw) -> dict:
    """Drive one policy spec over one trace; returns a result row.

    ``name`` is any registry spec (``"wtlfu-av?early_pruning=0"``); ``kw``
    carries build-time objects (``trace=`` for belady is added here).
    ``with_snapshots`` adds the engine's ``StatsSnapshot`` rows (the engine
    must be constructed with ``snapshot_every=``) as a ``"snapshots"`` list;
    ``limit`` caps driven accesses (the device-plane comparison rows trim
    the trace — per-decision kernel dispatch is the thing being measured,
    not trace length).
    """
    spec = PolicySpec.parse(name)
    if (
        spec.name.startswith("wtlfu")
        and "expected_entries" not in kw
        and "expected_entries" not in spec.params_dict
    ):
        kw["expected_entries"] = max(64, int(cap / max(1.0, trace.mean_object_size)))
    if spec.name == "belady":
        kw["trace"] = trace
    policy = REGISTRY.build(spec, cap, **kw)
    t0 = time.perf_counter()
    result = (engine or SimulationEngine()).run(policy, trace, limit=limit)
    st = result.stats
    wall = time.perf_counter() - t0
    row = {
        "policy": spec.to_string(),
        "trace": trace.name,
        "capacity": cap,
        "accesses": st.accesses,
        "hit_ratio": round(st.hit_ratio, 5),
        "byte_hit_ratio": round(st.byte_hit_ratio, 5),
        "victims_per_access": round(st.victims_per_access, 5),
        "used_frac": round(policy.used_bytes() / cap, 5),
        "us_per_access": round(wall / max(1, st.accesses) * 1e6, 3),
        "wall_s": round(wall, 3),
        "used_batch": result.used_batch,
        "data_plane": result.data_plane,
    }
    if with_snapshots:
        row["snapshots"] = snapshot_dicts(result.snapshots)
    return row


def snapshot_dicts(snapshots) -> list[dict]:
    """StatsSnapshot rows -> the plottable dicts the robustness JSON holds."""
    return [
        {
            "accesses": s.accesses,
            "hit_ratio": round(s.hit_ratio, 5),
            "byte_hit_ratio": round(s.byte_hit_ratio, 5),
            "interval_hit_ratio": round(s.interval_hit_ratio, 5),
            "used_bytes": s.used_bytes,
            "evictions": s.evictions,
        }
        for s in snapshots
    ]


def run_policies_fleet(jobs, trace, *, snapshot_every: "int | None" = None,
                       with_snapshots: bool = False) -> list[dict]:
    """Drive many W-TinyLFU configs over one trace as ONE fleet.

    ``jobs`` is a list of ``(spec, cap)`` pairs; every member is built with
    ``data_plane="device_full"`` and the whole grid advances through
    :class:`repro.kernels.fleet.FleetEngine` — one launch per
    shape-bucket per chunk instead of a sequential per-policy loop.
    Returns result rows parallel to ``jobs`` (same fields as
    :func:`run_policy`, plus ``mode="fleet"``; ``us_per_access`` is the
    fleet wall-clock amortized over all members' accesses).
    """
    from repro.kernels.fleet import FleetEngine

    eng = FleetEngine(snapshot_every=snapshot_every, collect_hits=False)
    members = []
    for name, cap in jobs:
        spec = PolicySpec.parse(name)
        kw = {}
        if "expected_entries" not in spec.params_dict:
            kw["expected_entries"] = max(
                64, int(cap / max(1.0, trace.mean_object_size)))
        policy = REGISTRY.build(spec, cap, data_plane="device_full", **kw)
        members.append((spec, cap, eng.add(
            policy, trace.keys, trace.sizes, label=spec.to_string())))
    t0 = time.perf_counter()
    eng.run()
    wall = time.perf_counter() - t0
    total = sum(m.policy.stats.accesses for _, _, m in members) or 1
    rows = []
    for spec, cap, m in members:
        st = m.policy.stats
        row = {
            "policy": spec.to_string(),
            "trace": trace.name,
            "capacity": cap,
            "accesses": st.accesses,
            "hit_ratio": round(st.hit_ratio, 5),
            "byte_hit_ratio": round(st.byte_hit_ratio, 5),
            "victims_per_access": round(st.victims_per_access, 5),
            "used_frac": round(m.policy.used_bytes() / cap, 5),
            "us_per_access": round(wall / total * 1e6, 3),
            "wall_s": round(wall, 3),
            "used_batch": True,
            "data_plane": "device_full",
            "mode": "fleet",
            "fleet_launches": eng.launches,
        }
        if with_snapshots:
            row["snapshots"] = snapshot_dicts(m.snapshots)
        rows.append(row)
    return rows


def emit(bench: str, rows: list[dict], derived_key: str = "hit_ratio") -> None:
    """Print CSV rows and persist JSON."""
    RESULTS_DIR.mkdir(exist_ok=True)
    with open(RESULTS_DIR / f"{bench}.json", "w") as f:
        json.dump(rows, f, indent=1)
    for r in rows:
        label = f"{bench}/{r.get('trace','-')}/{r.get('policy', r.get('label','-'))}/cap={r.get('capacity','-')}"
        print(f"{label},{r.get('us_per_access', 0)},{r.get(derived_key, '')}")
