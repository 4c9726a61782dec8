"""Paper Figure 13 + Table 2: policy CPU overhead.

Per the paper's method, the LRU wall-time in the same framework is subtracted
from each policy's wall-time to isolate *policy* overhead from simulation
plumbing; we report both raw us/access and LRU-subtracted overhead.

Two extra comparisons track the admission data plane release over release
in ``BENCH_overhead.json``:

* **Policy level** — W-TinyLFU under both admission data planes, for SLRU
  mains AND the sampled/random mains (counter-based victim sampling makes
  every eviction peek-stable, so the batched plane covers the full
  admission x eviction grid): ``data_plane=scalar`` (the reference
  per-victim walk) vs ``data_plane=batched`` (one ``estimate_batch`` call
  over the lazily gathered victim prefix). Decisions are byte-identical
  (``hit_ratio_matches_batched`` asserts it), so any delta is pure
  data-plane throughput. On the host sketch the scalar walk is the
  lightweight option (which is why ``auto`` picks it there); the batched
  rows quantify the abstraction cost. ``batched_speedup`` = scalar
  us/access ÷ batched us/access.
* **Sketch level** — the CMS-kernel backend scoring one N-key victim set:
  one batched ``estimate_batch`` call vs N scalar ``estimate`` calls. This
  is the data plane the batching is built for (one kernel dispatch instead
  of N); ``batched_speedup`` here is the headline batching win.
* **Whole-simulation device plane** — ``data_plane=device_full`` (the
  ENTIRE simulation step for a chunk of accesses in one ``lax.scan``:
  window hits, recency updates, miss cascade, adaptive climber, with the
  cache state device-resident between chunks) vs the scalar walk on the
  SAME CMS backend. Rows are measured **steady-state** (an untimed warm
  run first compiles every kernel variant), since jit compilation is a
  one-time cost the paper's CPU-overhead comparison is not about. Off-TPU
  these rows measure kernel semantics plus XLA-CPU dispatch, not
  accelerator speed, and a hard hit-ratio equality check fails the run if
  the planes ever stop deciding identically; ``speedup_vs_scalar`` is the
  scalar us/access over the device_full us/access.
"""

from __future__ import annotations

import time

from repro.core import REGISTRY, PolicySpec, SimulationEngine

from .common import PAPER_TRACES, emit, get_trace, run_policy, sequential_mode

POLICIES = ("lru", "wtlfu-av", "wtlfu-qv", "wtlfu-iv", "gdsf", "adaptsize", "lhd", "lrb")
FRACS = (0.001, 0.01, 0.1)
#: Policies run under both admission data planes (scalar vs batched): the
#: default-SLRU mains plus sampled/random mains — counter-based victim
#: sampling made every eviction peek-stable, so the batched plane covers
#: the whole grid (ISSUE 3) and these rows track its cost per combo.
DATA_PLANE_POLICIES = (
    "wtlfu-av",
    "wtlfu-qv",
    "wtlfu-iv",
    "wtlfu-av-sampled_frequency",
    "wtlfu-av-sampled_size",
    "wtlfu-qv-sampled_frequency_size",
    "wtlfu-qv-sampled_needed_size",
    "wtlfu-iv-random",
)
#: Victim-set sizes for the sketch-level data-plane comparison.
SKETCH_BATCH_SIZES = (8, 32, 128)
#: Accesses driven per device-plane row: enough decisions to amortize jit
#: compilation into the noise floor while keeping the off-TPU (XLA-CPU)
#: comparison affordable.
DEVICE_PLANE_LIMIT = 6_000
#: Specs for the whole-simulation comparison: sampled/random mains (the
#: in-scan sample walk) and the recency mains (LRU/SLRU, whose order the
#: device keeps as stamps).
DEVICE_FULL_POLICIES = (
    "wtlfu-qv-sampled_frequency",
    "wtlfu-av-sampled_frequency_size",
    "wtlfu-iv-random",
    "wtlfu-av-slru",
    "wtlfu-iv-lru",
)


def sketch_data_plane_rows(batch_sizes=SKETCH_BATCH_SIZES, repeats: int = 30) -> list[dict]:
    """CMS backend: one batched estimate_batch(N keys) vs N estimate calls."""
    from repro.core.cms_sketch import CMSSketch

    rows = []
    for n in batch_sizes:
        sk = CMSSketch(1024)
        keys = list(range(n))
        sk.increment_batch(keys)
        sk.flush()
        t0 = time.perf_counter()
        for _ in range(repeats):
            sk.estimate_batch(keys)
        batched_us = (time.perf_counter() - t0) / repeats * 1e6
        t0 = time.perf_counter()
        for _ in range(repeats):
            for k in keys:
                sk.estimate(k)
        scalar_us = (time.perf_counter() - t0) / repeats * 1e6
        rows.append({
            "label": f"cms_sketch_score_victims_n{n}",
            "batch_size": n,
            "us_per_access": round(batched_us, 1),  # one batched call
            "scalar_us": round(scalar_us, 1),  # n scalar calls
            "batched_speedup": round(scalar_us / max(1e-9, batched_us), 2),
            "data_plane": "batched_vs_scalar",
        })
    return rows


def device_full_rows(traces=("msr2",), frac=0.001,
                     limit=DEVICE_PLANE_LIMIT) -> list[dict]:
    """Whole-simulation-on-device vs the scalar walk, both on the CMS
    sketch backend.

    ``device_full`` resolves an entire access chunk — window hits,
    recency updates, the miss cascade — in ONE ``lax.scan`` launch with
    the cache state device-resident between chunks. Steady-state
    protocol: an untimed warm run compiles every shape bucket, then the
    timed run measures pure dispatch+execute. Hit ratios must match
    exactly (hard ``raise``; at ``limit`` accesses the 5-decimal rounding
    cannot mask a single differing decision). ``speedup_vs_scalar`` =
    scalar us/access over device_full us/access.
    """
    rows = []
    for tname in traces:
        tr = get_trace(tname)
        cap = max(1, int(tr.total_object_bytes * frac))
        ee = max(64, int(cap / max(1.0, tr.mean_object_size)))
        for pol in DEVICE_FULL_POLICIES:
            spec = PolicySpec.parse(pol)
            pair = {}
            for plane in ("scalar", "device_full"):
                sp = spec.with_params(data_plane=plane, sketch_backend="cms")
                SimulationEngine().run(
                    REGISTRY.build(sp, cap, expected_entries=ee), tr,
                    limit=limit)  # warm jit
                policy = REGISTRY.build(sp, cap, expected_entries=ee)
                t0 = time.perf_counter()
                res = SimulationEngine().run(policy, tr, limit=limit)
                wall = time.perf_counter() - t0
                st = res.stats
                rp = {
                    "policy": sp.to_string(),
                    "trace": tr.name,
                    "capacity": cap,
                    "frac": frac,
                    "accesses": st.accesses,
                    "hit_ratio": round(st.hit_ratio, 5),
                    "us_per_access": round(wall / max(1, st.accesses) * 1e6, 3),
                    "wall_s": round(wall, 3),
                    "data_plane": plane,
                    "warmed": True,
                }
                if plane == "device_full":
                    pipe = policy._device_pipeline
                    rp.update(
                        decisions=pipe.decisions,
                        chunk_calls=pipe.chunk_calls,
                        uploads=pipe.uploads,
                        resyncs=pipe.resyncs,
                    )
                pair[plane] = rp
                rows.append(rp)
            if pair["device_full"]["hit_ratio"] != pair["scalar"]["hit_ratio"]:
                raise AssertionError(
                    f"{pol}: device_full diverged from scalar "
                    f"({pair['device_full']['hit_ratio']} vs "
                    f"{pair['scalar']['hit_ratio']})"
                )
            pair["device_full"]["hit_ratio_matches_scalar"] = True
            pair["device_full"]["speedup_vs_scalar"] = round(
                pair["scalar"]["us_per_access"]
                / max(1e-9, pair["device_full"]["us_per_access"]),
                3,
            )
    return rows


#: Per-instance seeds of the fleet sweep: DEVICE_FULL_POLICIES x seeds
#: instances in one FleetEngine — the "whole policy grid in one launch"
#: claim, measured against the same instances run as a sequential loop.
FLEET_SEEDS = (0, 1, 2, 3)
#: Access-chunk size both arms of the fleet comparison run at. The fleet
#: claim is dispatch amortization, so the sweep measures the fine-chunk
#: operating point where per-launch overhead dominates the scan body and
#: a sequential loop pays it once per instance per chunk (the fleet once
#: per shape-bucket per chunk). Finer chunks are also the low-latency
#: end of the device plane's sync-cadence knob, not a synthetic setting.
#: At the default chunk (64) the scan body dominates, and a bucket's
#: launch costs about the sum of its lanes' scans.
FLEET_CHUNK = 8


def fleet_rows(traces=("msr2",), frac=0.001, seeds=FLEET_SEEDS,
               limit=DEVICE_PLANE_LIMIT, chunk=FLEET_CHUNK) -> list[dict]:
    """Vmapped fleet sweep vs the sequential ``device_full`` loop.

    The same ``len(DEVICE_FULL_POLICIES) * len(seeds)`` instances (every
    policy combo x per-instance seed, one shape-bucket per combo) are
    driven twice: once as the sequential per-policy loop the sweeps used
    to be, once stacked in one :class:`repro.kernels.fleet.FleetEngine`
    (one launch per shape-bucket per chunk), both at the same
    ``chunk`` (see :data:`FLEET_CHUNK`). Both arms are warmed untimed
    first. Per-instance hit ratios must match exactly (hard ``raise``);
    ``fleet_speedup`` = sequential wall over fleet wall — the tentpole
    number, from amortizing per-launch dispatch over the bucket.
    """
    import numpy as np

    from repro.kernels.fleet import FleetEngine

    rows = []
    for tname in traces:
        tr = get_trace(tname)
        cap = max(1, int(tr.total_object_bytes * frac))
        ee = max(64, int(cap / max(1.0, tr.mean_object_size)))
        specs = [
            PolicySpec.parse(pol).with_params(
                data_plane="device_full", sketch_backend="cms", seed=s)
            for pol in DEVICE_FULL_POLICIES for s in seeds
        ]
        keys = np.asarray(tr.keys[:limit], np.int64)
        sizes = np.asarray(tr.sizes[:limit], np.int64)

        def build(sp):
            return REGISTRY.build(sp, cap, expected_entries=ee, chunk=chunk)

        # sequential arm: warm (one instance per policy compiles its shape
        # bucket; seeds share the compiled kernels), then timed
        for sp in specs[:: len(seeds)]:
            SimulationEngine().run(build(sp), tr, limit=limit)
        t0 = time.perf_counter()
        seq = []
        for sp in specs:
            p = build(sp)
            SimulationEngine().run(p, tr, limit=limit)
            seq.append(p)
        seq_wall = time.perf_counter() - t0

        # fleet arm: warm, then timed
        warm = FleetEngine(collect_hits=False)
        for sp in specs:
            warm.add(build(sp), keys, sizes, label=sp.to_string())
        warm.run()
        eng = FleetEngine(collect_hits=False)
        members = [eng.add(build(sp), keys, sizes, label=sp.to_string())
                   for sp in specs]
        t0 = time.perf_counter()
        eng.run()
        fleet_wall = time.perf_counter() - t0

        total = sum(m.policy.stats.accesses for m in members) or 1
        speedup = round(seq_wall / max(1e-9, fleet_wall), 3)
        for sp, sp_seq, m in zip(specs, seq, members):
            hr_seq = round(sp_seq.stats.hit_ratio, 5)
            hr_fleet = round(m.policy.stats.hit_ratio, 5)
            if (hr_seq != hr_fleet
                    or sp_seq.stats.accesses != m.policy.stats.accesses):
                raise AssertionError(
                    f"{sp.to_string()}: fleet diverged from sequential "
                    f"device_full ({hr_fleet} vs {hr_seq})")
            rows.append({
                "policy": sp.to_string(),
                "trace": tr.name,
                "capacity": cap,
                "frac": frac,
                "accesses": m.policy.stats.accesses,
                "hit_ratio": hr_fleet,
                "us_per_access": round(fleet_wall / total * 1e6, 3),
                "wall_s": round(fleet_wall, 3),
                "data_plane": "device_full",
                "mode": "fleet",
                "chunk": chunk,
                "warmed": True,
                "hit_ratio_matches_sequential": True,
                "fleet_speedup": speedup,
            })
        rows.append({
            "label": "fleet_vs_sequential",
            "trace": tr.name,
            "capacity": cap,
            "chunk": chunk,
            "n_instances": len(specs),
            "fleet_launches": eng.launches,
            "sequential_wall_s": round(seq_wall, 3),
            "fleet_wall_s": round(fleet_wall, 3),
            "fleet_speedup": speedup,
        })
    return rows


def main(traces=PAPER_TRACES, fracs=FRACS) -> list[dict]:
    rows = []
    for tname in traces:
        tr = get_trace(tname)
        for frac in fracs:
            cap = max(1, int(tr.total_object_bytes * frac))
            lru_us = None
            for pol in POLICIES:
                r = run_policy(pol, tr, cap)
                if pol == "lru":
                    lru_us = r["us_per_access"]
                r["overhead_us"] = round(max(0.0, r["us_per_access"] - lru_us), 3)
                r["frac"] = frac
                rows.append(r)
            for pol in DATA_PLANE_POLICIES:
                # Same policy under each admission data plane:
                # byte-identical decisions, pure throughput delta.
                pair = {}
                for plane in ("batched", "scalar"):
                    rp = run_policy(f"{pol}?data_plane={plane}", tr, cap)
                    rp["overhead_us"] = round(max(0.0, rp["us_per_access"] - lru_us), 3)
                    rp["frac"] = frac
                    rp["data_plane"] = plane
                    pair[plane] = rp
                    rows.append(rp)
                pair["scalar"]["hit_ratio_matches_batched"] = (
                    pair["scalar"]["hit_ratio"] == pair["batched"]["hit_ratio"]
                )
                pair["batched"]["batched_speedup"] = round(
                    pair["scalar"]["us_per_access"]
                    / max(1e-9, pair["batched"]["us_per_access"]),
                    3,
                )
    rows.extend(device_full_rows())
    if not sequential_mode():
        rows.extend(fleet_rows())
    rows.extend(sketch_data_plane_rows())
    emit("overhead", rows, derived_key="overhead_us")
    return rows


if __name__ == "__main__":
    main()
