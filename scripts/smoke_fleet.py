#!/usr/bin/env python
"""Tier-1 CI smoke row for the fleet driver.

Fast end-to-end check (one workload-shift trace, a 4-instance grid) that
:class:`repro.kernels.fleet.FleetEngine`

* shape-buckets mixed specs and drives each bucket's chunk rounds in
  single fleet launches (launch count well under the members' summed
  chunk count),
* leaves every member byte-identical to the SAME spec driven through the
  sequential ``device_full`` loop — hit stream, ``CacheStats``, final
  contents, resync/upload counters — and
* restores host authority on release (plain scalar access works after).

Exits non-zero on any divergence; prints a one-line summary row. The
exhaustive mixed-grid fleet differential runs in the test suite — this is
the cheap always-on canary wired into ``.github/workflows/ci.yml``.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from repro.core import REGISTRY, HitMaskRecorder, SimulationEngine
from repro.kernels.fleet import FleetEngine
from repro.traces import make_trace

# one combo x four seeds: a single shape-bucket, so the canary pays ONE
# fleet compile (the mixed-bucket case is covered by the test suite)
SPECS = [f"wtlfu-av-slru?sketch_backend=cms&seed={s}" for s in (1, 2, 3, 4)]


def main() -> int:
    tr = make_trace("shift1", seed=11, scale=0.0005)
    keys, sizes = tr.keys, tr.sizes
    cap = max(1, int(tr.total_object_bytes * 0.02))
    ee = max(64, int(cap / tr.mean_object_size))

    def build(spec):
        return REGISTRY.build(spec, cap, data_plane="device_full",
                              expected_entries=ee, chunk=64)

    eng = FleetEngine()
    members = [eng.add(build(s), keys, sizes, label=s) for s in SPECS]
    t0 = time.perf_counter()
    eng.run()
    fleet_wall = time.perf_counter() - t0

    total_chunks = 0
    for spec, m in zip(SPECS, members):
        ref = build(spec)
        rec = HitMaskRecorder()
        SimulationEngine(instruments=(rec,)).run(ref, tr)
        ref.sync_deferred()  # host authority before content compares
        if not (rec.hits == m.hit_mask).all():
            print(f"FAIL: {spec}: hit/miss streams diverge", file=sys.stderr)
            return 1
        for field in ("accesses", "hits", "bytes_hit", "victims_examined",
                      "admissions", "rejections", "evictions"):
            if getattr(ref.stats, field) != getattr(m.policy.stats, field):
                print(f"FAIL: {spec}: stats.{field} diverges",
                      file=sys.stderr)
                return 1
        if ref.main.sizes != m.policy.main.sizes:
            print(f"FAIL: {spec}: final cache contents diverge",
                  file=sys.stderr)
            return 1
        if list(ref.window.items()) != list(m.policy.window.items()):
            print(f"FAIL: {spec}: window contents diverge", file=sys.stderr)
            return 1
        pa = ref._device_pipeline
        pb = m.policy._device_pipeline
        if dict(pa.resync_reasons) != dict(pb.resync_reasons) \
                or pa.uploads != pb.uploads:
            print(f"FAIL: {spec}: resync counters diverge "
                  f"({dict(pa.resync_reasons)}/{pa.uploads} vs "
                  f"{dict(pb.resync_reasons)}/{pb.uploads})", file=sys.stderr)
            return 1
        total_chunks += pb.chunk_calls
        if m.pipe._fleet_restore is not None:
            print(f"FAIL: {spec}: fleet hook not released", file=sys.stderr)
            return 1
        m.policy.access(10**12, 1)  # host-authoritative scalar path works

    if eng.launches >= total_chunks:
        print(f"FAIL: no amortization — {eng.launches} fleet launches "
              f"for {total_chunks} member chunks", file=sys.stderr)
        return 1
    print(
        f"smoke-fleet OK: n={len(SPECS)} accesses={len(keys)} "
        f"launches={eng.launches} member_chunks={total_chunks} "
        f"amortization={total_chunks / eng.launches:.2f}x "
        f"fleet_wall={fleet_wall:.2f}s"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
