#!/usr/bin/env python3
"""Smoke run of the cache data plane on one TPU chip, at full trace scale.

    python3 chip_smoke.py              # needs a TPU; exits non-zero without one
    python3 chip_smoke.py --rehearse   # CPU rehearsal: shrunk traces, Pallas
                                       # in interpret mode; never claims a TPU

Drives the three forms of the size-aware W-TinyLFU data plane through the
entry points a user calls (registry spec -> ``SimulationEngine`` /
``FleetEngine`` / ``PrefixCache``), all in this one process:

1. ``single``: ``data_plane=device_full`` over the whole msr2 trace (900k
   accesses) for an SLRU main and a sampled main. Each is checked against
   the scalar reference plane, run on the host CPU with the jnp sketch, over
   the first 100k accesses: hit stream, stats, sketch table, and main and
   window contents must be identical.
2. ``fleet``: the 21 admission x main combos over the first 200k accesses in
   one ``FleetEngine`` (past the cache fill and three sketch agings; the
   cut from the whole trace keeps the run inside its time limit). Each
   combo is its own one-lane bucket; two members must equal their
   sequential ``device_full`` runs.
3. ``sharded``: ``FleetEngine.sharded`` splits the same 200k accesses over
   4 shard caches of each single spec, one 4-lane bucket (one launch per
   round for all four); every shard must equal its sequential run.
4. ``serving``: the bursty prefix-cache load under sync and async admission;
   their request, token and byte hit ratios must be identical.

On the chip every plane must run compiled Pallas (``use_pallas`` and not
``interpret``), the first launch of each chunk program is also lowered with
its own arguments and must hold a ``tpu_custom_call``, and the donated
sketch table must be consumed by the first launch. Each phase
prints one JSON line of readings; the last line is
``{"ok": true, "device": {...}}``. A failed check raises, so the script
exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

MAINS = ("lru", "slru", "sampled_frequency", "sampled_size",
         "sampled_frequency_size", "sampled_needed_size", "random")
SINGLE_SPECS = (
    "wtlfu-av-slru?data_plane=device_full&chunk=256&seed=0x5EED",
    "wtlfu-qv-sampled_frequency_size?data_plane=device_full&chunk=256&seed=0x5EED",
)
FLEET_SPECS = tuple(
    f"wtlfu-{a}-{m}?data_plane=device_full&chunk=256&seed=0x5EED"
    for a in ("iv", "qv", "av") for m in MAINS)
STAT_FIELDS = ("accesses", "hits", "bytes_hit", "bytes_requested",
               "victims_examined", "admissions", "rejections", "evictions")
#: cache capacity as a share of the trace's total object bytes
CAPACITY_SHARE = 0.10
#: shard caches of the ``sharded`` phase, each with its share of capacity
SHARDS = 4

#: ``ref``: accesses checked against the scalar reference; ``fleet``:
#: accesses per fleet member. The rehearsal cuts scale only.
SIZES = {
    "chip": dict(trace_scale=1.0, ref=100_000, fleet=200_000, arrivals=1.0),
    "rehearse": dict(trace_scale=0.005, ref=2_000, fleet=1_000, arrivals=0.25),
}


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def emit(row: dict) -> None:
    print(json.dumps(row), flush=True)


class CompileMeter:
    """Counts the programs JAX compiles or loads from its persistent cache,
    and the seconds spent tracing, lowering and compiling them."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _TIMED = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration", _BACKEND)

    def __init__(self):
        import jax

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self._TIMED:
            self.seconds += secs
            self.compiles += event == self._BACKEND

    def _event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"

    def read(self) -> tuple[int, float, int]:
        return self.compiles, self.seconds, self.cache_hits

    def since(self, start) -> dict:
        c, s, h = self.read()
        return {"compiles": c - start[0], "compile_s": s - start[1],
                "compile_cache_hits": h - start[2]}


class Env:
    """What the run is on, and the sketch routing it asks for."""

    def __init__(self, rehearse: bool):
        import jax

        devs = jax.devices()
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
        self.on_tpu = self.device["platform"] == "tpu"
        self.sizes = SIZES["rehearse" if rehearse else "chip"]
        # on the chip the sketch picks compiled Pallas by itself; the CPU
        # rehearsal asks for Pallas, which runs there in interpret mode
        self.build_kw = {} if self.on_tpu else {"sketch_kwargs": {"use_pallas": True}}
        self.meter = CompileMeter()


def check_pallas(pol, env: Env, what: str) -> None:
    """The plane's sketch must run compiled Pallas on the chip (never the
    jnp fallback, never interpret mode)."""
    use_pallas = pol.sketch.use_pallas
    interpret = pol.admission_policy._device._interpret
    if env.on_tpu:
        check(use_pallas is True and interpret is False,
              f"{what}: use_pallas={use_pallas} interpret={interpret} on a TPU")
    else:
        check(interpret is True, f"{what}: compiled Pallas requested off-TPU")


@contextlib.contextmanager
def first_launch_lowered(module, name: str, env: Env, what: str):
    """Wrap the jitted chunk program ``module.name`` so its first launch is
    also lowered with that launch's own arguments (lowering reads shapes
    only, so the donated buffers are untouched). On the chip the program
    must call the Pallas kernel; the rehearsal lowers too, so the shapes
    are checked before a chip run. Yields the lowered launch's arguments."""
    real = getattr(module, name)
    first: list = []

    def probe(*args, **kw):
        if not first:
            first.append(args)
            text = real.lower(*args, **kw).as_text()
            if env.on_tpu:
                check("tpu_custom_call" in text,
                      f"{what}: no tpu_custom_call in the lowered {name}")
        return real(*args, **kw)

    setattr(module, name, probe)
    try:
        yield first
    finally:
        setattr(module, name, real)
    check(bool(first), f"{what}: {name} never launched")


def cache_state(pol) -> dict:
    """Everything the byte-for-byte comparison reads (flushes the sketch's
    pending increments, which every later estimate would flush anyway)."""
    import numpy as np

    pol.sync_deferred()
    pol.sketch.flush()
    return {"stats": {f: getattr(pol.stats, f) for f in STAT_FIELDS},
            "main": dict(pol.main.sizes), "window": list(pol.window.items()),
            "table": np.asarray(pol.sketch.table),
            "resets": pol.sketch.resets}


def compare_states(a: dict, b: dict, what: str) -> None:
    import numpy as np

    for f in STAT_FIELDS:
        check(a["stats"][f] == b["stats"][f],
              f"{what}: stats.{f} {a['stats'][f]} != {b['stats'][f]}")
    check(a["main"] == b["main"], f"{what}: main contents differ")
    check(a["window"] == b["window"], f"{what}: window contents differ")
    check(a["resets"] == b["resets"], f"{what}: sketch resets differ")
    check(np.array_equal(a["table"], b["table"]), f"{what}: sketch tables differ")


def plane_counters(pol) -> dict:
    pipe = pol._device_pipeline
    return {"launches": pipe.chunk_calls,
            "scan_steps": pipe.chunk_calls * pipe.scan_len(),
            "uploads": pipe.uploads,
            "resyncs": pipe.resyncs,
            "resync_reasons": dict(pipe.resync_reasons),
            "per_decision_dispatches": pol.admission_policy._device.calls}


def reference_run(spec: str, trace, cap: int, ee: int):
    """The scalar plane over ``trace`` on the host CPU with the jnp sketch:
    no call of it reaches the accelerator."""
    import jax

    from repro.core import REGISTRY, HitMaskRecorder, SimulationEngine

    with jax.default_device(jax.devices("cpu")[0]):
        ref = REGISTRY.build(spec, cap, data_plane="scalar",
                             sketch_backend="cms", expected_entries=ee,
                             sketch_kwargs={"use_pallas": False})
        rec = HitMaskRecorder()
        SimulationEngine(instruments=(rec,)).run(ref, trace)
        return rec.hits, cache_state(ref)


def phase_single(spec: str, tr, cap: int, ee: int, env: Env) -> dict:
    import numpy as np

    from repro.core import REGISTRY, HitMaskRecorder, Instrument, SimulationEngine
    from repro.kernels import device_full

    ref_n = env.sizes["ref"]

    class Checkpoint(Instrument):
        """Captures the cache at exactly ``ref_n`` accesses, and the clock
        and compile count there (the end of the warm-up)."""

        def __init__(self):
            self.done = 0
            self.state = None

        def on_chunk(self, policy, keys, sizes, hits):
            self.done += len(keys)
            if self.done == ref_n:
                self.state = cache_state(policy)
                self.meter0 = env.meter.read()
                self.t = time.perf_counter()

    pol = REGISTRY.build(spec, cap, expected_entries=ee, **env.build_kw)
    check_pallas(pol, env, spec)
    table0 = pol.sketch.table
    rec, cp = HitMaskRecorder(), Checkpoint()
    m0 = env.meter.read()
    t0 = time.perf_counter()
    with first_launch_lowered(device_full, "_simulate_chunk", env, spec):
        SimulationEngine(chunk_size=ref_n // 10,
                         instruments=(rec, cp)).run(pol, tr)
    pol.sync_deferred()
    t1 = time.perf_counter()
    check(cp.state is not None, "checkpoint never reached")
    row = {"phase": "single", "spec": spec, "accesses": len(tr),
           "hit_ratio": pol.stats.hit_ratio,
           "byte_hit_ratio": pol.stats.byte_hit_ratio, "wall_s": t1 - t0,
           "accesses_per_s": len(tr) / (t1 - t0),
           "steady_accesses_per_s": (len(tr) - ref_n) / (t1 - cp.t),
           **env.meter.since(m0),
           "compiles_after_warmup": env.meter.read()[0] - cp.meter0[0],
           **plane_counters(pol)}
    check(pol.stats.accesses == len(tr), "not every access was driven")
    if env.on_tpu:
        check(table0.is_deleted(),
              "the donated sketch table outlived the first launch")

    t = time.perf_counter()
    ref_hits, ref_state = reference_run(spec, tr.slice(ref_n), cap, ee)
    row["reference_s"] = time.perf_counter() - t
    check(np.array_equal(ref_hits, rec.hits[:ref_n]),
          f"{spec}: hit stream differs from the scalar reference "
          f"(first at access {int(np.argmax(ref_hits != rec.hits[:ref_n]))})")
    compare_states(ref_state, cp.state, f"{spec} at {ref_n} accesses")
    row["reference_match_accesses"] = ref_n
    return row


def check_sequential(member, spec: str, cap: int, ee: int, env: Env,
                     what: str):
    """A fleet member must equal its own sequential ``device_full`` run over
    its trace: one engine chunk over the whole of it, so launch boundaries
    (and so the counters) line up with the fleet's. Returns the scan steps
    the sequential run launched."""
    import numpy as np

    from repro.core import REGISTRY, AccessTrace, HitMaskRecorder, SimulationEngine

    seq = REGISTRY.build(spec, cap, expected_entries=ee, **env.build_kw)
    rec = HitMaskRecorder()
    trace = AccessTrace(what, member.keys, member.sizes)
    SimulationEngine(chunk_size=max(1, len(trace)),
                     instruments=(rec,)).run(seq, trace)
    check(np.array_equal(rec.hits, member.hit_mask),
          f"{what}: hit stream differs from its sequential run")
    compare_states(cache_state(seq), cache_state(member.policy), what)
    a, b = seq._device_pipeline, member.pipe
    check((dict(a.resync_reasons), a.uploads)
          == (dict(b.resync_reasons), b.uploads),
          f"{what}: resync/upload counters differ")
    return a.chunk_calls * a.scan_len()


def phase_fleet(tr, cap: int, ee: int, env: Env) -> dict:
    from repro.core import REGISTRY
    from repro.kernels import fleet
    from repro.kernels.fleet import FleetEngine

    n = env.sizes["fleet"]
    prefix = tr.slice(n)
    eng = FleetEngine()
    members = {}
    for spec in FLEET_SPECS:
        pol = REGISTRY.build(spec, cap, expected_entries=ee, **env.build_kw)
        check_pallas(pol, env, spec)
        members[spec] = eng.add(pol, prefix.keys, prefix.sizes, label=spec)
    m0 = env.meter.read()
    t0 = time.perf_counter()
    with first_launch_lowered(fleet, "_fleet_chunk", env, "fleet"):
        eng.run()
    wall = time.perf_counter() - t0
    planes = [m.pipe for m in members.values()]
    ratios = [m.policy.stats.hit_ratio for m in members.values()]
    row = {"phase": "fleet", "members": len(members),
           "buckets": len({tuple(sorted(p._statics(m.policy).items()))
                           for p, m in zip(planes, members.values())}),
           "accesses": n * len(members), "wall_s": wall,
           "accesses_per_s": n * len(members) / wall,
           "hit_ratio_min": min(ratios), "hit_ratio_max": max(ratios),
           **env.meter.since(m0), "launches": eng.launches,
           "member_chunks": sum(p.chunk_calls for p in planes),
           "uploads": sum(p.uploads for p in planes),
           "resyncs": sum(p.resyncs for p in planes)}
    for m in members.values():
        check(m.policy.stats.accesses == n, f"{m.label}: not fully driven")
    for spec in SINGLE_SPECS:
        check_sequential(members[spec], spec, cap, ee, env, f"fleet {spec}")
    row["sequential_match"] = list(SINGLE_SPECS)
    return row


def phase_sharded(spec: str, tr, cap: int, ee: int, env: Env) -> dict:
    """A hash-partitioned deployment: ``SHARDS`` shard caches of ``spec``,
    each with its share of capacity, as one multi-lane fleet bucket (one
    launch per round). ``scan_step_us`` is the launch time (wall
    minus compile) per scan step: against ``sequential_step_us`` it shows
    what one step costs for all lanes at once."""
    from repro.core import REGISTRY
    from repro.kernels import fleet
    from repro.kernels.fleet import FleetEngine

    prefix = tr.slice(env.sizes["fleet"])
    scap, see = cap // SHARDS, max(64, ee // SHARDS)
    pols = [REGISTRY.build(spec, scap, expected_entries=see, **env.build_kw)
            for _ in range(SHARDS)]
    for pol in pols:
        check_pallas(pol, env, f"sharded {spec}")
    eng = FleetEngine.sharded(pols, prefix.keys, prefix.sizes)
    m0 = env.meter.read()
    t0 = time.perf_counter()
    with first_launch_lowered(fleet, "_fleet_chunk", env,
                              f"sharded {spec}") as first:
        eng.run()
    wall = time.perf_counter() - t0
    comp = env.meter.since(m0)
    lanes = first[0][0].shape[0]
    check(lanes == SHARDS and eng.launches < sum(p._device_pipeline.chunk_calls
                                                 for p in pols),
          f"sharded {spec}: shards did not share launches")
    pad = pols[0]._device_pipeline.scan_len()
    row = {"phase": "sharded", "spec": spec, "lanes": lanes,
           "accesses": len(prefix), "shard_accesses":
           [len(m.keys) for m in eng.members], "wall_s": wall,
           "accesses_per_s": len(prefix) / wall,
           "hit_ratios": [p.stats.hit_ratio for p in pols], **comp,
           "launches": eng.launches,
           "scan_step_us": (wall - comp["compile_s"])
           / (eng.launches * pad) * 1e6,
           "resyncs": sum(p._device_pipeline.resyncs for p in pols)}
    for m in eng.members:
        check(m.policy.stats.accesses == len(m.keys),
              f"{m.label}: not fully driven")
    m1 = env.meter.read()
    t1 = time.perf_counter()
    steps = sum(check_sequential(m, spec, scap, see, env,
                                 f"sharded {spec} {m.label}")
                for m in eng.members)
    seq = env.meter.since(m1)
    row["sequential_s"] = time.perf_counter() - t1
    row["sequential_step_us"] = (row["sequential_s"]
                                 - seq["compile_s"]) / steps * 1e6
    row["sequential_match"] = [m.label for m in eng.members]
    return row


def phase_serving(env: Env) -> dict:
    from benchmarks.serving_cache import LOAD_POLICY, run_load
    from repro.kernels import admission as admission_plane
    from repro.kernels import device_full
    from repro.traces import ARRIVAL_SPECS, make_arrivals

    trace = make_arrivals(ARRIVAL_SPECS["bursty_small"], seed=0,
                          scale=env.sizes["arrivals"])
    row = {"phase": "serving", "policy": LOAD_POLICY, "requests": len(trace)}
    for admission in ("sync", "async"):
        m0 = env.meter.read()
        # sync admission decides one launch per decision; async drains
        # whole access chunks, one chunk-program launch each
        module, name = ((admission_plane, "_decide_sampled") if admission == "sync"
                        else (device_full, "_simulate_chunk"))
        with first_launch_lowered(module, name, env, f"serving {admission}"):
            res, cache = run_load(LOAD_POLICY, admission, trace)
        check_pallas(cache.policy, env, f"serving {admission}")
        cache.pool.check_invariants()
        pipe = cache.policy._device_pipeline
        row[admission] = {
            "accesses": cache.policy.stats.accesses,
            "hit_ratio": cache.policy.stats.hit_ratio,
            **{k: res[k] for k in ("request_hit_ratio", "token_hit_ratio",
                                   "byte_hit_ratio", "wall_s",
                                   "decision_p50_ms", "decision_p99_ms")},
            "requests_per_s": len(trace) / res["wall_s"],
            **env.meter.since(m0), "launches": pipe.chunk_calls,
            "per_decision_dispatches": cache.policy.admission_policy._device.calls,
            "resyncs": pipe.resyncs,
            "resync_reasons": dict(pipe.resync_reasons)}
    for k in ("request_hit_ratio", "token_hit_ratio", "byte_hit_ratio"):
        check(row["sync"][k] == row["async"][k],
              f"serving: {k} sync {row['sync'][k]} != async {row['async'][k]}")
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at a shrunk scale, Pallas in "
                         "interpret mode (the last line names the CPU)")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from repro.compile_cache import use_compile_cache

    cache_dir = use_compile_cache(ROOT)
    env = Env(args.rehearse)
    if not env.on_tpu and not args.rehearse:
        print(f"chip_smoke: no TPU (JAX found {env.device}); "
              "--rehearse runs the CPU rehearsal", file=sys.stderr)
        return 2

    import jax

    from repro.traces import make_trace

    emit({"phase": "setup", "jax": jax.__version__, "device": env.device,
          "compile_cache": cache_dir, "sizes": env.sizes})
    tr = make_trace("msr2", seed=0, scale=env.sizes["trace_scale"])
    cap = int(tr.total_object_bytes * CAPACITY_SHARE)
    ee = max(64, int(cap / tr.mean_object_size))
    for spec in SINGLE_SPECS:
        emit(phase_single(spec, tr, cap, ee, env))
    emit(phase_fleet(tr, cap, ee, env))
    for spec in SINGLE_SPECS:
        emit(phase_sharded(spec, tr, cap, ee, env))
    emit(phase_serving(env))
    emit({"ok": True, "device": env.device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
