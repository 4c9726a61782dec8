"""The benchmark harness: one cell, one run, one result line.

A cell names a configuration (``chipbench/configs/<config>.json``: the
policy spec the program builds and the parameters of the plain reference)
and a traffic mix (``chipbench/traffic/<traffic>.json``: the trace class, the
generator module that draws it, the capacity rule, shards and batch size).
Per-layer metrics are read by ``chipbench/metrics/<metric>.py``. The harness
finds all of them by the names in ``BENCHMARK.json`` and holds nothing of
any one cell.

A run: set-up (traffic from the seed, the caches built through the
registry, warm-up batches until every cache is full and its sketch has aged
``warmup_agings`` times), a timed window of ``--seconds`` in batches, and
after the window the check against the plain reference, which replays every
access the run drove.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import time
from pathlib import Path

import numpy as np

import trace_reduce

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

#: every number the check compares is exact: its limit is 0
LIMITS = {"hit_mismatch": 0, "stats_mismatch": 0, "sketch_mismatch": 0,
          "contents_mismatch": 0, "partition_mismatch": 0}


class HarnessError(RuntimeError):
    """A fault of the run itself (no chip, traffic exhausted, bad cell)."""


def process_age() -> float:
    """Seconds since this process started (Linux ``/proc``; 10 ms grain)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    return (time.clock_gettime(time.CLOCK_BOOTTIME)
            - start_ticks / os.sysconf("SC_CLK_TCK"))


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise HarnessError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the cell, as data ----------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    root: Path  # the checkout the cell's files are found in

    def file(self, *parts) -> Path:
        return self.root.joinpath("chipbench", *parts)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in bench["workloads"]}.get(name)
    if wl is None:
        raise HarnessError(f"no workload {name!r} in BENCHMARK.json")
    cfg = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads(
        (root / "chipbench" / "traffic" / f"{wl['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in reported else [])]
    return Cell(name, int(wl["chips"]), config, traffic, e2e, per_layer, Path(root))


# -- traffic and sizing ------------------------------------------------------------

@dataclasses.dataclass
class Sizing:
    capacity: int  # bytes, per cache
    entries: int  # capacity over mean object size, per cache
    shards: int


def pow2_at_least(x: int, floor: int) -> int:
    w = floor
    while w < x:
        w <<= 1
    return w


def sketch_size(entries: int, rule: dict) -> tuple[int, int]:
    """The configuration's sketch for ``entries``: rows
    ``pow2(counters_per_entry * entries)`` wide, aged every
    ``sample_per_entry * entries`` increments."""
    return (pow2_at_least(rule["counters_per_entry"] * entries, rule["min_width"]),
            rule["sample_per_entry"] * entries)


def build_cache(spec: str, capacity: int, entries: int, rule: dict, sketch_kwargs: dict):
    """One cache through the registry, its sketch as the configuration
    states. The program sizes a sketch ``pow2(expected_entries)`` wide and
    ages it every ``sample_factor * expected_entries``, which cannot give
    every configured period; so the period is set on the built sketch,
    before its first increment."""
    from repro.core import REGISTRY

    width, sample = sketch_size(entries, rule)
    pol = REGISTRY.build(spec, capacity, expected_entries=width,
                         sketch_kwargs=sketch_kwargs)
    pol.sketch.sample_size = sample
    if pol.sketch.width != width:
        raise HarnessError(f"the program sized a sketch {pol.sketch.width} wide, "
                           f"not {width}")
    return pol


def make_traffic(cell: Cell, seed: int, seconds: float, scale: float = 1.0):
    """The run's access stream and the cache sizing, all from the seed."""
    tr = cell.traffic
    gen = load_module(cell.file("traffic", f"{tr['generator']}.py"),
                      f"chipbench_traffic_{tr['generator']}")
    cls = dict(tr["class"])
    if scale != 1.0:  # rehearsal only: shrink the class, keep its shape
        cls["n_accesses"] = max(1000, int(cls["n_accesses"] * scale))
        cls["n_objects"] = max(100, int(cls["n_objects"] * scale))
    stream = gen.Stream(cls, seed, tr.get("universe_seed"))
    total, mean = stream.class_trace_stats()
    capacity = int(total * tr["capacity_share"])
    entries = max(64, int(capacity / mean))
    shards = int(tr["shards"])
    n = int(tr["warmup_max_accesses"] * scale) + math.ceil(
        seconds * tr["stream_accesses_per_s"])
    keys, sizes = stream.take(n)
    return keys, sizes, Sizing(capacity // shards, max(64, entries // shards), shards)


# -- the window ----------------------------------------------------------------------

class Window:
    """Runs batches: warm-up until ``warm()`` holds (or ``warm_max``
    accesses), then a timed window of
    ``seconds``. Each window batch is timed from hand-over to hit mask on
    the host. With a trace directory, the profiler records window batches
    ``trace_from`` .. ``trace_from + trace_batches``."""

    def __init__(self, seconds: float, agings: int, warm_max: int, *, trace_dir=None,
                 trace_from: int = 0, trace_batches: int = 0, meter=None):
        self.seconds = seconds
        self.agings = agings
        self.warm = lambda: True  # set by drive() once its caches exist
        self.warm_max = warm_max
        self.trace_dir = trace_dir
        self.trace_from = trace_from
        self.trace_batches = trace_batches
        self.meter = meter
        self.phase = "warmup"
        self.warm_accesses = 0
        self.warmed = False
        self.latencies: list[float] = []
        self.accesses = 0
        self.traced_accesses = 0
        self.t0 = self.t1 = None
        self.setup_s = None
        self.on_open = []  # callbacks at window open
        self._tracing = False
        self._compiles0 = None

    def _open(self):
        self.phase = "window"
        for cb in self.on_open:
            cb()
        self.setup_s = process_age()
        if self.meter is not None:
            self._compiles0 = self.meter.read()
        self.t0 = time.perf_counter()

    def step(self, batch) -> bool:
        """Run one batch (``batch()`` returns its access count) unless the
        window is over; False stops the drive loop."""
        import jax

        if self.phase == "warmup":
            self.warmed = self.warm()
            if self.warmed or self.warm_accesses >= self.warm_max:
                self._open()
        if self.phase == "done":
            return False
        if self.phase == "window":
            i = len(self.latencies)
            if time.perf_counter() - self.t0 >= self.seconds:
                self._close()
                return False
            if self.trace_dir and i == self.trace_from:
                t = time.perf_counter()
                jax.profiler.start_trace(self.trace_dir)
                self.t0 += time.perf_counter() - t  # the profiler's, not the window's
                self._tracing = True
                self._span = jax.profiler.TraceAnnotation("traced_window")
                self._span.__enter__()
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("batch"):
            n = batch()
        dt = time.perf_counter() - t
        if self.phase == "warmup":
            self.warm_accesses += n
            return True
        self.latencies.append(dt)
        self.accesses += n
        if self._tracing:
            self.traced_accesses += n
            if len(self.latencies) >= self.trace_from + self.trace_batches:
                self._stop_trace()
        return True

    def _stop_trace(self):
        """Stop the profiler. Its seconds (the trace is written out here) do
        not count to the window, so a traced run drives the window's whole
        length, its agings and resyncs with it."""
        import jax

        self._span.__exit__(None, None, None)
        t = time.perf_counter()
        jax.profiler.stop_trace()
        if self.phase == "window":
            self.t0 += time.perf_counter() - t
        self._tracing = False

    def _close(self):
        self.t1 = time.perf_counter()
        self.phase = "done"
        if self._tracing:
            self._stop_trace()
        if self.meter is not None:
            c0, h0 = self._compiles0
            c1, h1 = self.meter.read()
            self.compiles_in_window = c1 - c0
            self.cache_loads_in_window = h1 - h0

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


class CompileMeter:
    """Counts backend compiles and persistent-cache loads."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        self.compiles += event == "/jax/core/compile/backend_compile_duration"

    def _event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"

    def read(self):
        return self.compiles, self.cache_hits


# -- host spans around the plane's calls -------------------------------------------

#: (module, class, method) of the host plane whose calls get a span
SPANNED = (("repro.kernels.device_full", "DeviceFullSimulationPlane", "ensure_host"),
           ("repro.kernels.device_full", "DeviceFullSimulationPlane", "_dispatch"),
           ("repro.kernels.device_full", "DeviceFullSimulationPlane", "_collect"))


@contextlib.contextmanager
def plane_spans():
    """Wrap each method in :data:`SPANNED` in a ``TraceAnnotation`` of its
    name, from outside. A method that is gone gets no span."""
    import jax

    undo = []
    for modname, clsname, meth in SPANNED:
        try:
            cls = getattr(importlib.import_module(modname), clsname)
            real = cls.__dict__[meth]
        except (ImportError, AttributeError, KeyError):
            continue

        def wrapped(*a, _real=real, _name=meth, **kw):
            with jax.profiler.TraceAnnotation(_name):
                return _real(*a, **kw)

        setattr(cls, meth, wrapped)
        undo.append((cls, meth, real))
    try:
        yield
    finally:
        for cls, meth, real in undo:
            setattr(cls, meth, real)


# -- the system under test ------------------------------------------------------------

@dataclasses.dataclass
class Outcome:
    """What the program produced: per cache, its driven keys and sizes, its
    hit stream and its end state; and the plane counters."""
    caches: list  # dicts: keys, sizes, hits, state, routed (shard keys)
    counters: dict


STAT_FIELDS = ("accesses", "hits", "bytes_hit", "bytes_requested",
               "victims_examined", "admissions", "rejections", "evictions")


def policy_state(pol) -> dict:
    """The end state the check compares, read through the policy's public
    surface (host authority restored first)."""
    pol.sync_deferred()
    pol.sketch.flush()
    return {"stats": {f: int(getattr(pol.stats, f)) for f in STAT_FIELDS},
            "table": np.asarray(pol.sketch.table),
            "resets": int(pol.sketch.resets),
            "window": [(int(k), int(s)) for k, s in pol.window.items()],
            "main": [(int(k), int(s), int(g)) for k, s, g in pol.main.export_rows()]}


def _plane(pol):
    return getattr(pol, "_device_pipeline", None)


def _counter(pols, name):
    vals = [getattr(_plane(p), name, None) for p in pols]
    return None if any(v is None for v in vals) else sum(vals)


def drive(cell: Cell, keys, sizes, sz: Sizing, win: Window, sketch_kwargs: dict) -> Outcome:
    """Build the cell's caches through the registry and run the window over
    them: one ``SimulationEngine`` call per batch for a single cache, one
    ``FleetEngine.sharded`` round per batch for shards."""
    from repro.core import AccessTrace, Instrument, SimulationEngine

    class Recorder(Instrument):
        """Keeps every hit mask the engine hands back."""

        def __init__(self):
            self.chunks = []

        def on_chunk(self, policy, keys, sizes, hits):
            self.chunks.append(np.asarray(hits, bool).copy())

    spec = cell.config["spec"]
    batch = int(cell.traffic["batch"])
    rule = cell.config["reference"]["sketch"]
    pols = [build_cache(spec, sz.capacity, sz.entries, rule, sketch_kwargs)
            for _ in range(sz.shards)]
    counters = {}
    opened = []  # per cache: the accesses driven when the window opened
    win.on_open.append(lambda: counters.update(resyncs0=_counter(pols, "resyncs")))
    win.warm = lambda: all(p.sketch.resets >= win.agings and p.stats.evictions > 0
                           for p in pols)
    if sz.shards == 1:
        pol = pols[0]
        rec = Recorder()
        eng = SimulationEngine(chunk_size=batch, instruments=(rec,))
        pos = 0

        def one():
            nonlocal pos
            if pos + batch > len(keys):
                raise HarnessError("traffic ran out before the window ended")
            eng.run(pol, AccessTrace("batch", keys[pos:pos + batch],
                                     sizes[pos:pos + batch]))
            pos += batch
            return batch

        host_n = int(cell.traffic.get("host_warmup_accesses", 0))

        def host_warm():
            """The program's per-access host path, which each aging
            boundary of the window takes once: its decision programs, one
            for each victim count, compile here and not in the window. One
            batch after it puts the state back on the device."""
            nonlocal pos
            if pos + host_n + batch > len(keys):
                raise HarnessError("traffic ran out before the window ended")
            rec.chunks.append(np.fromiter(
                (pol.access(int(k), int(s)) for k, s in
                 zip(keys[pos:pos + host_n], sizes[pos:pos + host_n])), bool, host_n))
            pos += host_n
            win.warm_accesses += host_n + one()

        if host_n:
            win.on_open.insert(0, host_warm)
        win.on_open.append(lambda: opened.append([pos]))
        while win.step(one):
            pass
        hits = np.concatenate(rec.chunks)
        caches = [{"keys": keys[:pos], "sizes": sizes[:pos], "hits": hits,
                   "window_from": opened[0][0], "state": policy_state(pol)}]
    else:
        from repro.kernels.fleet import FleetEngine

        eng = FleetEngine.sharded(pols, keys, sizes)
        real_step = eng._step

        def one_round(b):
            before = sum(m.pos for m in eng.members)
            if not real_step(b) or any(m.exhausted for m in eng.members):
                raise HarnessError("traffic ran out before the window ended")
            return sum(m.pos for m in eng.members) - before

        eng._step = lambda b: win.step(lambda: one_round(b))
        win.on_open.append(lambda: opened.append([m.pos for m in eng.members]))
        eng.run()
        caches = [{"keys": m.keys[:m.pos], "sizes": m.sizes[:m.pos],
                   "hits": m.hit_mask, "routed": m.keys, "window_from": opened[0][k],
                   "state": policy_state(m.policy)} for k, m in enumerate(eng.members)]
    counters["resyncs"] = _counter(pols, "resyncs")
    plane = _plane(pols[0])
    counters["scan_len"] = plane.scan_len() if hasattr(plane, "scan_len") else None
    counters["lanes"] = sz.shards
    counters["sketch_width"] = int(caches[0]["state"]["table"].shape[1])
    return Outcome(caches, counters)


# -- the check ---------------------------------------------------------------------

def _list_mismatch(a: list, b: list) -> int:
    return sum(x != y for x, y in zip(a, b)) + abs(len(a) - len(b))


def check(cell: Cell, out: Outcome, keys, sz: Sizing):
    """Replay every driven access through the plain reference and count
    each difference; also returns how many of the window's answers differ."""
    refcfg = cell.config["reference"]
    ref = load_module(cell.file("reference", f"{refcfg['module']}.py"),
                      f"chipbench_reference_{refcfg['module']}")
    counts = dict.fromkeys(["hit_mismatch", "stats_mismatch", "sketch_mismatch",
                            "contents_mismatch"], 0)
    if sz.shards > 1:
        counts["partition_mismatch"] = 0
        part = ref.shard_of(keys, sz.shards, refcfg.get("shard_salt", 0))
    window_wrong = 0
    for k, c in enumerate(out.caches):
        rkeys, rsizes = c["keys"], c["sizes"]
        if sz.shards > 1:
            mine = keys[part == k]
            counts["partition_mismatch"] += _list_mismatch(
                c["routed"].tolist(), mine.tolist())
        cache = ref.Cache(refcfg, sz.capacity, sz.entries)
        hits = cache.run(rkeys, rsizes)
        rs = cache.state()
        ps = c["state"]
        wrong = hits != c["hits"][:len(hits)]
        counts["hit_mismatch"] += int(wrong.sum()) + abs(len(hits) - len(c["hits"]))
        window_wrong += int(wrong[c["window_from"]:].sum())
        counts["stats_mismatch"] += sum(ps["stats"][f] != rs["stats"][f] for f in STAT_FIELDS)
        if ps["table"].shape == rs["table"].shape:
            counts["sketch_mismatch"] += int((ps["table"] != rs["table"]).sum())
        else:
            counts["sketch_mismatch"] += int(rs["table"].size)
        counts["sketch_mismatch"] += abs(ps["resets"] - rs["resets"])
        counts["contents_mismatch"] += _list_mismatch(ps["window"], rs["window"])
        counts["contents_mismatch"] += _list_mismatch(ps["main"], rs["main"])
    return counts, window_wrong


# -- per-layer metrics -----------------------------------------------------------------

def read_metrics(cell: Cell, ctx) -> dict:
    """Every per-layer metric the cell declares. One whose reader finds
    nothing to read is an error of the run, not a number left out: a
    renamed program or kernel would otherwise silence it unseen."""
    out, silent = {}, []
    for m in cell.per_layer:
        reader = load_module(cell.file("metrics", f"{m['name']}.py"),
                             f"chipbench_metric_{m['name']}")
        v = reader.read(ctx)
        if v is None:
            silent.append(m["name"])
        else:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    if silent:
        log = getattr(ctx, "log", None)
        if log and getattr(ctx, "trace", None) is not None:
            import names

            log(f"trace: {names.describe(ctx.trace)}")
        raise HarnessError(f"per-layer metrics {silent} of {cell.name} read nothing: "
                           "a name in chipbench/names.py or a counter of the plane "
                           "is gone from the program")
    return out


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise HarnessError(f"no peaks for device kind {kind!r} in chipbench/peaks.json")
    return table["devices"][kind]


# -- one run ---------------------------------------------------------------------------

@dataclasses.dataclass
class Measured:
    """One run up to the close of its window: the cell, the traffic, what
    the program produced, the window and (traced) the reduced trace."""
    cell: Cell
    keys: np.ndarray
    sizes: np.ndarray
    sizing: Sizing
    out: Outcome
    win: Window
    device: dict
    reduced: "trace_reduce.Trace | None"


def measure(workload: str, seed: int, seconds: float, trace: bool, *,
            rehearse: bool = False, scale: float = 1.0, keep_trace=None,
            root: Path = ROOT) -> Measured:
    """Set-up, warm-up and the timed window of one run of one cell.
    ``rehearse`` runs on the CPU with the jnp sketch."""
    import tempfile

    import jax

    cell = load_cell(workload, root)
    devs = jax.devices()
    platform = devs[0].platform
    if not rehearse and (platform != "tpu" or len(devs) < cell.chips):
        raise HarnessError(
            f"needs {cell.chips} TPU chip(s); JAX found {len(devs)} {platform} device(s)")
    if not rehearse:
        from repro.compile_cache import use_compile_cache

        use_compile_cache(root)
        # every program, however quick to compile, goes to the persistent
        # cache, so a later run of the cell in this checkout compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    meter = CompileMeter()
    keys, sizes, sz = make_traffic(cell, seed, seconds, scale)
    tr = cell.traffic
    sketch_kwargs = {"use_pallas": False} if rehearse else {}
    reduced = None
    with contextlib.ExitStack() as stack:
        trace_dir = None
        if trace:
            trace_dir = stack.enter_context(tempfile.TemporaryDirectory())
            stack.enter_context(plane_spans())
        win = Window(seconds, int(tr["warmup_agings"]),
                     int(tr["warmup_max_accesses"] * scale), trace_dir=trace_dir,
                     trace_from=int(tr["trace_from"]),
                     trace_batches=int(tr["trace_batches"]), meter=meter)
        out = drive(cell, keys, sizes, sz, win, sketch_kwargs)
        if trace:
            path = trace_reduce.find_xplane(trace_dir)
            if keep_trace:
                Path(keep_trace).parent.mkdir(parents=True, exist_ok=True)
                shutil.copy(path, keep_trace)
            reduced = trace_reduce.load(path)
    dev = devs[0]
    mem = (dev.memory_stats() or {}) if platform == "tpu" else {}
    device = {"platform": platform, "kind": dev.device_kind, "count": cell.chips,
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    # the program's caches went with ``drive``
    gc.collect()
    return Measured(cell, keys, sizes, sz, out, win, device, reduced)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        rehearse: bool = False, scale: float = 1.0, keep_trace=None,
        root: Path = ROOT, log=None) -> dict:
    """One run of one cell; returns the result object (the last stdout line).
    ``rehearse`` runs on the CPU with the jnp sketch and reports no metric."""
    import types

    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    m = measure(workload, seed, seconds, trace, rehearse=rehearse, scale=scale,
                keep_trace=keep_trace, root=root)
    cell, win, out, device, reduced = m.cell, m.win, m.out, m.device, m.reduced
    lat = sorted(win.latencies)
    log(f"warm-up: {win.warm_accesses} accesses; every cache full and aged: {win.warmed}")
    log(f"window: {win.accesses} accesses in {len(lat)} batches over {win.window_s} s; "
        f"compiles in window {win.compiles_in_window}, "
        f"persistent-cache loads in window {win.cache_loads_in_window}")
    metrics, extra = {}, {}
    if not rehearse and not trace:
        q = statistics.quantiles(lat, n=100, method="inclusive")
        log(f"batch_p95_ms over {len(lat)} batch samples (p50 {q[49] * 1e3} ms)")
        vals = {"accesses_per_s": win.accesses / win.window_s,
                "batch_p95_ms": q[94] * 1e3, "setup_s": win.setup_s}
        metrics = {e["name"]: {"value": vals[e["name"]], "unit": e["unit"]}
                   for e in cell.end_to_end}
    if trace and not rehearse:
        ctx = types.SimpleNamespace(
            trace=reduced, counters=out.counters, window_accesses=win.accesses,
            traced_accesses=win.traced_accesses, config=cell.config,
            peaks=peaks_for(device["kind"]), log=log)
        metrics = read_metrics(cell, ctx)
        device["busy_s"] = reduced.busy_seconds()
        device["window_s"] = reduced.window_seconds()
        extra["breakdown"] = reduced.breakdown()
    t = time.perf_counter()
    counts, window_wrong = check(cell, out, m.keys, m.sizing)
    log(f"reference: {sum(len(c['keys']) for c in out.caches)} accesses replayed "
        f"in {time.perf_counter() - t} s")
    correct = all(v <= LIMITS[k] for k, v in counts.items()) and win.accesses > 0
    checks = {k: {"value": v, "limit": LIMITS[k]} for k, v in counts.items()}
    for k, v in checks.items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    return {"correct": bool(correct), "attempted": win.accesses,
            "failed": window_wrong, "metrics": metrics, "device": device,
            **extra, "checks": checks}
