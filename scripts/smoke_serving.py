#!/usr/bin/env python
"""Tier-1 CI smoke row for the serving admission pipeline.

Fast end-to-end check (<30s: one small fixed-seed arrival trace) that

* the async admission pipeline stays byte-identical to the synchronous
  per-access baseline — same entries, same hit ratios, same policy stats,
* deferred chunk launches actually engage (deferred dispatches > 0 and
  fewer chunk launches than decisions),
* the shared BlockPool survives with its refcount invariants intact, and
* the cache operates in a sane regime (nonzero hit ratio, bounded
  decision latency).

Exits non-zero on any divergence; prints a one-line summary row. The
exhaustive serving differential tests run in the suite — this is the
cheap always-on canary wired into ``.github/workflows/ci.yml``.
"""

from __future__ import annotations

import sys
import time

from repro.serving import PrefixCache, PrefixCacheConfig
from repro.traces import ARRIVAL_SPECS, make_arrivals

SPEC = "wtlfu-av-sampled_frequency?data_plane=device_full&chunk=16&sketch_backend=cms"
BPT = 2 * 3 * 64 * 2  # smollm-class per-token KV bytes
BLOCK = 16


def drive(admission: str, trace) -> PrefixCache:
    working_set = sum(
        {int(t): int(ln) for t, ln in zip(trace.template, trace.template_len)}.values()
    ) * BPT
    cache = PrefixCache(PrefixCacheConfig(
        capacity_bytes=max(BPT * BLOCK * 8, int(working_set * 0.2)),
        block_size=BLOCK, bytes_per_token=BPT, policy=SPEC,
        admission=admission))
    for i in range(len(trace)):
        tmpl, ln = int(trace.template[i]), int(trace.template_len[i])
        tokens = [tmpl * 1_000_003 + j for j in range(ln)]
        cache.lookup(tokens + [10**9 + i * 100 + j
                               for j in range(int(trace.suffix_len[i]))])
        full = (ln // BLOCK) * BLOCK
        if full:
            cache.offer(tokens[:full])
    cache.sync()
    cache.pool.check_invariants()
    return cache


def main() -> int:
    trace = make_arrivals(ARRIVAL_SPECS["bursty_small"], seed=7, scale=0.5)
    t0 = time.perf_counter()
    sync = drive("sync", trace)
    a = drive("async", trace)
    wall = time.perf_counter() - t0

    for k in ("request_hit_ratio", "token_hit_ratio", "byte_hit_ratio"):
        if getattr(sync, k) != getattr(a, k):
            print(f"FAIL: {k} diverges: {getattr(sync, k)} vs {getattr(a, k)}",
                  file=sys.stderr)
            return 1
    if set(sync.entries) != set(a.entries):
        print("FAIL: resident entries diverge", file=sys.stderr)
        return 1
    for field in ("accesses", "hits", "admissions", "rejections", "evictions"):
        if getattr(sync.policy.stats, field) != getattr(a.policy.stats, field):
            print(f"FAIL: policy stats.{field} diverges", file=sys.stderr)
            return 1
    if sync.request_hit_ratio < 0.1:
        print(f"FAIL: degenerate regime — hit ratio {sync.request_hit_ratio}",
              file=sys.stderr)
        return 1
    m = a.admission.metrics()
    if m["deferred_dispatches"] == 0:
        print("FAIL: async pipeline never deferred a chunk launch",
              file=sys.stderr)
        return 1
    if m["chunk_calls"] >= m["decisions"]:
        print(f"FAIL: {m['chunk_calls']} launches for {m['decisions']} "
              "decisions — chunk batching is not engaging", file=sys.stderr)
        return 1
    if m["decision_p99_ms"] > 30_000:
        print(f"FAIL: decision p99 {m['decision_p99_ms']}ms out of bounds",
              file=sys.stderr)
        return 1
    print(
        f"smoke-serving OK: hit_ratio={sync.request_hit_ratio:.3f} "
        f"token_hit_ratio={sync.token_hit_ratio:.3f} "
        f"deferred={m['deferred_dispatches']} chunks={m['chunk_calls']} "
        f"decisions={m['decisions']} p99={m['decision_p99_ms']:.1f}ms "
        f"wall={wall:.1f}s"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
