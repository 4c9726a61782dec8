"""Serving integration benchmark: the paper's admission policies managing an
LLM prefix cache (our first-class integration; DESIGN.md §2).

Synthetic request stream: a Zipf-popular population of prompt *templates*
(system prompts / few-shot headers of very different lengths — the
variable-size regime), each request = template + unique user suffix.
Objects = template prefixes; size ∝ tokens x per-arch KV bytes.

Metrics per policy: request hit ratio (paper hit-ratio analog),
token hit ratio (byte-hit-ratio analog = prefill compute saved),
us/request policy overhead. Bookkeeping-level (no tensors) so streams are
large; tensor-level correctness is covered by tests/test_serving.py.
"""

from __future__ import annotations

import os
import time

import numpy as np

from repro.configs import get_config
from repro.serving import (
    PrefixCache,
    PrefixCacheConfig,
    Request,
    Scheduler,
    SchedulerConfig,
    kv_bytes_per_token,
)
from repro.traces import ARRIVAL_SPECS, make_arrivals

from .common import bench_scale, emit

POLICIES = ("lru", "wtlfu-av", "wtlfu-qv", "wtlfu-iv", "gdsf", "adaptsize", "lhd")
ARCHS = ("command-r-35b", "deepseek-v2-lite-16b", "smollm-135m")


def make_stream(n_requests: int, seed: int = 0):
    """(template_id, template_len, suffix_len) per request."""
    rng = np.random.default_rng(seed)
    n_templates = 400
    # template lengths: mixture of short chat headers and huge few-shot docs
    lens = np.where(
        rng.random(n_templates) < 0.7,
        rng.integers(64, 512, n_templates),
        rng.integers(2048, 16384, n_templates),
    )
    pmf = (np.arange(1, n_templates + 1) ** -0.9)
    pmf /= pmf.sum()
    ids = rng.choice(n_templates, size=n_requests, p=pmf)
    suffix = rng.integers(8, 64, size=n_requests)
    return ids, lens, suffix


def run_policy(policy: str, arch: str, n_requests: int, ws_frac: float) -> dict:
    """``ws_frac``: cache capacity as a fraction of the template working
    set's KV bytes (the contended regime the paper studies)."""
    cfg = get_config(arch)
    bpt = kv_bytes_per_token(cfg)
    ids, lens, suffix = make_stream(n_requests)
    templates = [
        [tid * 1_000_003 + j for j in range(int(lens[tid]))] for tid in range(len(lens))
    ]
    working_set = int(lens.sum()) * bpt
    capacity = max(bpt * 64, int(working_set * ws_frac))
    cache = PrefixCache(
        PrefixCacheConfig(
            capacity_bytes=capacity, block_size=16, bytes_per_token=bpt, policy=policy
        )
    )
    t0 = time.perf_counter()
    for i in range(n_requests):
        tokens = templates[int(ids[i])]
        cache.lookup(tokens + [10**9 + i * 100 + j for j in range(int(suffix[i]))])
        cache.offer(tokens)
    wall = time.perf_counter() - t0
    s = cache.stats()
    s.update(
        arch=arch,
        policy=policy,
        trace=f"serving-{arch}",
        capacity=capacity,
        ws_frac=ws_frac,
        hit_ratio=s["request_hit_ratio"],
        byte_hit_ratio=s["token_hit_ratio"],
        us_per_access=round(wall / n_requests * 1e6, 2),
        bytes_per_token=bpt,
    )
    return s


def main() -> list[dict]:
    n_requests = max(400, int(20_000 * bench_scale()))
    rows = []
    for arch in ARCHS:
        for ws_frac in (0.05, 0.2):
            for policy in POLICIES:
                rows.append(run_policy(policy, arch, n_requests, ws_frac))
    emit("serving_cache", rows, derived_key="token_hit_ratio")
    return rows


# ---------------------------------------------------------------------------
# End-to-end load benchmark (ISSUE 6): bursty multi-tenant open-loop
# arrivals driven through scheduler -> prefix cache, with the admission
# pipeline either synchronous (per-access verdicts, the baseline) or async
# (deferred device_full chunk launches). Measures sustained
# requests/sec, p50/p99 admission-decision latency, queue depth, and the
# three hit ratios; appended to BENCH_serving.json by benchmarks.run.
# ---------------------------------------------------------------------------

#: Device W-TinyLFU: the paper's AV discipline over a sampled main, one
#: lax.scan launch per chunk of accesses.
LOAD_POLICY = "wtlfu-av-sampled_frequency?data_plane=device_full&chunk=64&sketch_backend=cms"
LOAD_ARCH = "smollm-135m"
MAX_NEW_TOKENS = 16


def _prompt(template: int, tmpl_len: int, rid: int, suffix_len: int) -> list:
    tokens = [template * 1_000_003 + j for j in range(tmpl_len)]
    tokens += [10**9 + rid * 100 + j for j in range(suffix_len)]
    return tokens


def run_load(policy: str, admission: str, trace, *, arch: str = LOAD_ARCH,
             ws_frac: float = 0.15, chunk: "int | None" = None,
             block_size: int = 16, max_running: int = 16):
    """Drive one arrival trace end to end: submit on the arrival clock,
    schedule (live KV blocks from the shared pool, preempting under
    pressure), look up / offer each prefilled prompt, decode to
    completion. Pure bookkeeping — wall time is dominated by the
    admission path, which is the thing under test. Returns the result
    row and the driven :class:`PrefixCache`."""
    cfg = get_config(arch)
    bpt = kv_bytes_per_token(cfg)
    tmpl_lens = {}
    for t, ln in zip(trace.template.tolist(), trace.template_len.tolist()):
        tmpl_lens[t] = ln
    working_set = sum(tmpl_lens.values()) * bpt
    capacity = max(bpt * block_size * 8, int(working_set * ws_frac))
    # live-KV headroom: the pool is shared between cached prefixes and the
    # scheduler's live blocks — reserve peak live demand (max_running
    # concurrent requests at the worst-case length) beyond the cache
    # capacity so steady-state decoding doesn't cannibalize the cache;
    # only demand spikes past the reserve reclaim cached prefixes
    max_req_tokens = (int(trace.template_len.max())
                      + int(trace.suffix_len.max()) + MAX_NEW_TOKENS)
    headroom = max_running * -(-max_req_tokens // block_size)
    cache = PrefixCache(PrefixCacheConfig(
        capacity_bytes=capacity, block_size=block_size, bytes_per_token=bpt,
        policy=policy, admission=admission, admission_chunk=chunk,
        pool_headroom_blocks=headroom))
    sched = Scheduler(SchedulerConfig(max_running=max_running,
                                      prefill_token_budget=1 << 30),
                      pool=cache.pool, block_size=block_size)
    preempts = 0
    starve = 0
    n = len(trace)
    t0 = time.perf_counter()

    def step():
        nonlocal preempts, starve
        before = sched.alloc_failures
        to_prefill, _ = sched.schedule()
        if sched.alloc_failures > before:
            # pool pressure: decode progress frees blocks within
            # MAX_NEW_TOKENS steps, so only preempt (recompute-style,
            # newest victim loses least work) on sustained starvation —
            # preempting eagerly livelocks: the victim re-queues at the
            # head and steals the blocks right back
            starve += 1
            if starve > 2 * MAX_NEW_TOKENS and sched.running:
                sched.preempt(sched.running[-1])
                preempts += 1
                starve = 0
        else:
            starve = 0
        for req in to_prefill:
            cached, entry = cache.lookup(req.prompt)
            req.cached_tokens = cached
            full = (len(req.prompt) // block_size) * block_size
            if full:
                cache.offer(req.prompt[:full])
            sched.on_prefilled(req)
        for req in list(sched.running):
            sched.on_token(req, 0)

    # open-loop drive: the arrival clock (not service progress) decides
    # when requests join — a burst lands several arrivals inside one
    # scheduler step, deepening the queues exactly as live traffic would
    times = trace.t_arrive
    step_dt = float(times[-1] - times[0]) / max(1, n // 4) or 1e-6
    t_sim = float(times[0])
    i = 0
    while i < n or sched.has_work:
        while i < n and float(times[i]) <= t_sim:
            sched.submit(Request(
                i, _prompt(int(trace.template[i]), int(trace.template_len[i]),
                           i, int(trace.suffix_len[i])), MAX_NEW_TOKENS))
            i += 1
        step()
        t_sim += step_dt
        if i < n and not sched.has_work:
            t_sim = max(t_sim, float(times[i]))  # idle gap: jump ahead
    cache.sync()
    wall = time.perf_counter() - t0

    s = cache.stats()
    adm = s.pop("admission", {})
    row = {
        "bench": "serving_load",
        "policy": policy,
        "arch": arch,
        "admission": admission,
        "trace": trace_name(trace),
        "n_requests": n,
        "capacity": capacity,
        "requests_per_sec": round(n / wall, 1),
        "wall_s": round(wall, 3),
        "request_hit_ratio": s["request_hit_ratio"],
        "token_hit_ratio": s["token_hit_ratio"],
        "byte_hit_ratio": s["byte_hit_ratio"],
        "decision_p50_ms": adm.get("decision_p50_ms", 0.0),
        "decision_p99_ms": adm.get("decision_p99_ms", 0.0),
        "max_queue_depth": adm.get("max_queue_depth", 0),
        "mean_queue_depth": adm.get("mean_queue_depth", 0.0),
        "preemptions": preempts,
        "pool_reclaims": cache.pool.reclaims,
        "stale_rewalks": s["stale_rewalks"],
        "us_per_access": round(wall / max(1, n) * 1e6, 2),
    }
    cache.pool.check_invariants()
    return row, cache


def trace_name(trace) -> str:
    return getattr(trace, "_name", "bursty")


def load_main(quick: "bool | None" = None) -> list[dict]:
    """The registered ``serving`` benchmark: async pipeline vs the
    synchronous per-access baseline on the same device_full policy
    spec (byte-identical decisions by construction — asserted), plus a
    host-plane async row for context."""
    if quick is None:
        quick = os.environ.get("REPRO_BENCH_QUICK", "") not in ("", "0")
    spec_name = "bursty_small" if quick else "bursty_multitenant"
    scale = 1.0 if quick else max(0.1, min(1.0, bench_scale() * 12.5))
    spec = ARRIVAL_SPECS[spec_name]
    trace = make_arrivals(spec, seed=0, scale=scale)
    object.__setattr__(trace, "_name", spec.name)

    # warmup: one untimed pass per mode on the SAME trace/capacity — the
    # decision-kernel jit cache keys on mirror and sketch shapes, which
    # depend on capacity and grow with entry count, so only an identical
    # configuration covers every shape the timed run will hit
    for adm in ("sync", "async"):
        run_load(LOAD_POLICY, adm, trace)

    rows = []
    sync_row, _ = run_load(LOAD_POLICY, "sync", trace)
    async_row, _ = run_load(LOAD_POLICY, "async", trace)
    rows += [sync_row, async_row]
    rows.append(run_load("wtlfu-av", "async", trace)[0])  # host-plane context

    # acceptance: equal hit ratios (byte-identical decisions), higher
    # sustained request rate for the async pipeline
    for k in ("request_hit_ratio", "token_hit_ratio", "byte_hit_ratio"):
        assert sync_row[k] == async_row[k], (
            f"async/sync {k} diverged: {sync_row[k]} vs {async_row[k]}")
    assert async_row["requests_per_sec"] > sync_row["requests_per_sec"], (
        "async admission pipeline should sustain more requests/sec than "
        f"the synchronous baseline: {async_row['requests_per_sec']} <= "
        f"{sync_row['requests_per_sec']}")
    emit("serving_load", rows, derived_key="requests_per_sec")
    return rows


if __name__ == "__main__":
    main()
    load_main()
