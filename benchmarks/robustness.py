"""Paper Figs. 11-12: robustness — hit-ratio-over-time curves.

The paper's robustness argument is that size-aware W-TinyLFU tracks the
best policy *throughout* a trace, not just on the end-to-end average, while
heavyweight adaptive policies (AdaptSize's Markov reconfiguration, LHD's
ranked sampling) can lag behind workload shifts. This benchmark drives each
policy with the engine's periodic :class:`StatsSnapshot` rows and emits one
row per (trace, policy) holding the whole curve: cumulative and
per-interval hit ratio every ``SNAPSHOT_POINTS``-th of the trace.

JSON lands in ``benchmarks/results/robustness.json``; each row's
``snapshots`` list is directly plottable as Fig. 11/12-style curves
(x = accesses, y = interval_hit_ratio).

Besides the paper's four trace classes, the sweep includes the synthetic
**workload-shift** traces (``repro.traces.SHIFT_SPECS``): abrupt
mid-trace phase changes in key popularity and size distribution, the
adversarial case for slow-adapting policies — shift rows carry the phase
boundary indices so plots can mark them.
"""

from __future__ import annotations

from repro.core import SimulationEngine
from repro.traces import SHIFT_SPECS, shift_boundaries

from .common import (PAPER_TRACES, bench_scale, emit, get_trace,
                     run_policies_fleet, run_policy, sequential_mode)

POLICIES = ("wtlfu-av", "wtlfu-qv", "wtlfu-iv", "lru", "gdsf", "adaptsize", "lhd")
TRACES = PAPER_TRACES + tuple(sorted(SHIFT_SPECS))
FRACS = (0.01, 0.1)
SNAPSHOT_POINTS = 20  # snapshots per run
#: sharded-deployment sketch: one shift trace hash-partitioned over K
#: cache shards (each a device_full instance in the same fleet)
SHARDED_TRACE = "shift1"
SHARDED_SHARDS = 4
SHARDED_SPEC = "wtlfu-av"


def _finish_row(r: dict, tname: str, frac: float, snapshot_every: int) -> dict:
    r["frac"] = frac
    r["snapshot_every"] = snapshot_every
    if tname in SHIFT_SPECS:
        r["phase_boundaries"] = shift_boundaries(tname, scale=bench_scale())
    # Fig. 11/12 headline: how far the worst interval sags below
    # the mean (lower sag = more robust over time).
    intervals = [s["interval_hit_ratio"] for s in r["snapshots"]]
    if intervals:
        r["min_interval_hit_ratio"] = round(min(intervals), 5)
        r["max_interval_hit_ratio"] = round(max(intervals), 5)
    return r


def sharded_rows(tname=SHARDED_TRACE, n_shards=SHARDED_SHARDS,
                 spec=SHARDED_SPEC, frac=0.01) -> list[dict]:
    """Hash-partitioned deployment curves: one trace split over
    ``n_shards`` cache shards (aggregate + per-shard hit ratios), the
    whole fleet advancing in one launch per bucket."""
    from repro.core import REGISTRY, PolicySpec
    from repro.kernels.fleet import FleetEngine

    tr = get_trace(tname)
    snapshot_every = max(1, len(tr) // (n_shards * SNAPSHOT_POINTS))
    cap = max(1, int(tr.total_object_bytes * frac / n_shards))  # per shard
    ps = PolicySpec.parse(spec)
    ee = max(64, int(cap / max(1.0, tr.mean_object_size)))
    shards = [REGISTRY.build(ps, cap, data_plane="device_full",
                             expected_entries=ee)
              for _ in range(n_shards)]
    eng = FleetEngine.sharded(shards, tr.keys, tr.sizes,
                              snapshot_every=snapshot_every,
                              collect_hits=False)
    eng.run()
    from .common import snapshot_dicts

    rows = []
    agg_acc = sum(p.stats.accesses for p in shards)
    agg_hits = sum(p.stats.hits for p in shards)
    for m in eng.members:
        st = m.policy.stats
        rows.append({
            "policy": ps.to_string(), "trace": tr.name, "capacity": cap,
            "shard": m.label, "n_shards": n_shards, "frac": frac,
            "accesses": st.accesses,
            "hit_ratio": round(st.hit_ratio, 5),
            "byte_hit_ratio": round(st.byte_hit_ratio, 5),
            "data_plane": "device_full", "mode": "fleet_sharded",
            "snapshots": snapshot_dicts(m.snapshots),
        })
    rows.append({
        "policy": ps.to_string(), "trace": tr.name, "capacity": cap,
        "shard": "aggregate", "n_shards": n_shards, "frac": frac,
        "accesses": agg_acc,
        "hit_ratio": round(agg_hits / agg_acc if agg_acc else 0.0, 5),
        "data_plane": "device_full", "mode": "fleet_sharded",
    })
    return rows


def main(traces=TRACES, fracs=FRACS, policies=POLICIES) -> list[dict]:
    rows = []
    for tname in traces:
        tr = get_trace(tname)
        snapshot_every = max(1, len(tr) // SNAPSHOT_POINTS)
        caps = {frac: max(1, int(tr.total_object_bytes * frac))
                for frac in fracs}
        fleet = {}
        wtlfu = [(pol, frac) for frac in fracs for pol in policies
                 if pol.startswith("wtlfu")]
        if wtlfu and not sequential_mode():
            try:
                frows = run_policies_fleet(
                    [(pol, caps[frac]) for pol, frac in wtlfu], tr,
                    snapshot_every=snapshot_every, with_snapshots=True)
                fleet = dict(zip(wtlfu, frows))
            except ValueError as e:
                # e.g. trace objects past the device_full int32 size
                # bound — this trace keeps the per-policy loop
                print(f"# fleet path unavailable for {tname}: {e}")
        for frac in fracs:
            for pol in policies:
                r = fleet.get((pol, frac))
                if r is None:
                    engine = SimulationEngine(snapshot_every=snapshot_every)
                    r = run_policy(pol, tr, caps[frac], engine=engine,
                                   with_snapshots=True)
                rows.append(_finish_row(r, tname, frac, snapshot_every))
    rows.extend(sharded_rows())
    emit("robustness", rows, derived_key="min_interval_hit_ratio")
    return rows


if __name__ == "__main__":
    main()
