"""Fleet-scale simulation: N independent ``device_full`` caches advanced
by ONE launch per shape-bucket per chunk.

PR 7 made a simulation chunk a pure device function
(:func:`repro.kernels.device_full._simulate_chunk_impl` — state in, state
out, no host round-trips mid-chunk). This module stacks N independent
cache instances — each with its own seed, capacity, admission/eviction
combo, trace slice, and adaptive-window carry — along a leading batch
axis and resolves a chunk for a **whole bucket** in one jitted launch
with donated stacked buffers (a ``lax.map`` of the chunk scan over the
lanes, see :func:`_fleet_chunk`).

Shape-bucketing
---------------
A launch needs a common shape and a common set of static kernel
arguments, so members are grouped into *buckets* keyed on the
kernel statics (eviction discipline/rule/sample width, main kind,
adaptive flag, sketch saturation cap, pallas routing) **plus the sketch
table shape** — CMS tables cannot be padded (the width participates in
hash indexing). Within a bucket, Main/Window slot arrays CAN be padded:
every kernel op masks by the live counts ``n``/``wn``, so zero-padding
lanes to the bucket-wide maximum is semantically inert. Each bucket
launches independently; a fleet of B buckets costs B launches per round,
not N.

Per-instance resyncs
--------------------
The two host-resync reasons are handled per-lane without stalling the
fleet: an **aging** resync on instance i materializes only lane i back
to the host (via the plane's ``_fleet_restore`` hook), replays the
boundary access through the host path, and re-uploads that lane into the
stack on the next round; a **mirror_grow** on instance i bumps its
*logical* slot count through the plane's own pre-flight (so resync
counters stay byte-identical to a sequential run) and pads the shared
physical stack only when the logical size exceeds it.

Everything a sequential ``device_full`` run observes — admission
decisions, ``CacheStats``, cache contents, upload and resync counters —
is byte-identical per instance (asserted in the differential suite and
the ``scripts/smoke_fleet.py`` canary).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import StatsSnapshot
from repro.kernels.device_full import (
    DeviceFullSimulationPlane,
    _InFlightSim,
    _limbs_of,
    _simulate_chunk_impl,
)

__all__ = ["FleetEngine", "FleetMember", "fleet_plane_of"]


@functools.partial(
    jax.jit,
    static_argnames=("discipline", "rule", "sample", "early_pruning",
                     "adaptive", "main_kind", "cap", "use_pallas", "interpret"),
    donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7, 8, 9),
)
def _fleet_chunk(table, mk_hi, mk_lo, msz, mstamp, mseg,
                 wk_hi, wk_lo, wsz, wstamp,
                 xs_hi, xs_lo, xs_sz, scal, key_limbs,
                 *, discipline, rule, sample, early_pruning, adaptive,
                 main_kind, cap, use_pallas, interpret):
    """One trace chunk for a whole shape-bucket: every positional buffer
    carries a leading lane axis; per-lane take lengths ride in
    ``scal[:, a_n]`` (invalid scan iterations are masked in the kernel, so
    ragged and even zero-length lanes are exact no-ops).

    The lanes run one after another inside the launch (``lax.map`` of the
    single-cache program), not vectorized: under ``vmap`` every
    ``lax.cond`` of the scan becomes a select that runs both branches on
    every lane at every access. On a TPU v5e a 4-lane ``vmap`` bucket,
    even with each branch rewritten as a guarded ``while_loop``, cost as
    much per lane-step as sequential lanes for an SLRU main and 13x more
    for a sampled main; the map costs about a quarter less than
    sequential lanes for both."""
    f = functools.partial(
        _simulate_chunk_impl, discipline=discipline, rule=rule, sample=sample,
        early_pruning=early_pruning, adaptive=adaptive, main_kind=main_kind,
        cap=cap, use_pallas=use_pallas, interpret=interpret)
    return jax.lax.map(lambda lane: f(*lane), (
        table, mk_hi, mk_lo, msz, mstamp, mseg, wk_hi, wk_lo, wsz, wstamp,
        xs_hi, xs_lo, xs_sz, scal, key_limbs))


@functools.partial(jax.jit, donate_argnums=tuple(range(10)))
def _scatter_lanes(table, m0, m1, m2, m3, m4, w0, w1, w2, w3,
                   idx, trows, mrows, wrows):
    """Scatter freshly uploaded lanes into the stacked buffers in ONE
    dispatch (an unjitted ``.at[i].set`` per array costs ~1ms of host
    dispatch each; ten per upload dominated the fleet wall-clock)."""
    mains = [m0, m1, m2, m3, m4]
    wins = [w0, w1, w2, w3]
    return (table.at[idx].set(trows),
            tuple(a.at[idx].set(r) for a, r in zip(mains, mrows)),
            tuple(a.at[idx].set(r) for a, r in zip(wins, wrows)))


@jax.jit
def _gather_lane(table, m0, m1, m2, m3, m4, w0, w1, w2, w3, i):
    """Slice one lane out of the stacked buffers in ONE dispatch (the
    per-lane aging-resync restore path)."""
    return (table[i], (m0[i], m1[i], m2[i], m3[i], m4[i]),
            (w0[i], w1[i], w2[i], w3[i]))


def fleet_plane_of(policy) -> DeviceFullSimulationPlane:
    """The policy's ``device_full`` plane, or raise: fleet members must be
    built with ``data_plane="device_full"``."""
    pipe = getattr(policy, "_device_pipeline", None)
    if not isinstance(pipe, DeviceFullSimulationPlane):
        raise ValueError(
            "FleetEngine members must be built with data_plane='device_full' "
            f"(got {getattr(policy, 'data_plane', None)!r})")
    return pipe


class FleetMember:
    """One enrolled cache instance: its policy, its trace slice, and the
    demuxed per-instance results (hit stream + snapshots)."""

    __slots__ = ("policy", "pipe", "keys", "sizes", "khi", "klo", "pos",
                 "label", "hits", "snapshots", "_snap_acc", "bucket", "lane")

    def __init__(self, policy, keys, sizes, label):
        self.policy = policy
        self.pipe = fleet_plane_of(policy)
        self.keys = np.ascontiguousarray(np.asarray(keys, np.int64))
        self.sizes = np.ascontiguousarray(np.asarray(sizes, np.int64))
        if self.keys.shape != self.sizes.shape:
            raise ValueError("keys and sizes must have equal length")
        if len(self.sizes) and int(self.sizes.max()) > self.pipe.device.max_size:
            raise ValueError(
                f"device_full plane: object size {int(self.sizes.max())} "
                f"exceeds the exact-arithmetic bound {self.pipe.device.max_size}")
        self.khi, self.klo = _limbs_of(self.keys)
        self.pos = 0
        self.label = label
        self.hits: list[np.ndarray] = []
        self.snapshots: list[StatsSnapshot] = []
        self._snap_acc = 0
        self.bucket = None
        self.lane = -1

    @property
    def exhausted(self) -> bool:
        return self.pos >= len(self.keys)

    @property
    def hit_mask(self) -> np.ndarray:
        """The per-access hit stream driven so far (requires the engine's
        ``collect_hits``)."""
        if not self.hits:
            return np.zeros(0, dtype=bool)
        return np.concatenate(self.hits)


class _Bucket:
    """One shape-bucket: members sharing kernel statics + sketch shape,
    with their state stacked along the lane axis."""

    __slots__ = ("statics", "members", "table", "main", "window",
                 "slots", "wslots")

    def __init__(self, statics):
        self.statics = statics
        self.members: list[FleetMember] = []
        self.table = None  # [N, ROWS, width]
        self.main = None  # 5 x [N, slots]
        self.window = None  # 4 x [N, wslots]
        self.slots = 0
        self.wslots = 0


class FleetEngine:
    """Batches chunk streaming for N ``device_full`` instances into one
    launch per shape-bucket per round, demuxing stats, hit
    streams, and snapshots per instance.

    Usage::

        eng = FleetEngine()
        for spec, cap in grid:
            p = REGISTRY.build(spec, cap, data_plane="device_full", ...)
            eng.add(p, trace.keys, trace.sizes, label=spec)
        eng.run()          # all members driven to trace end
        eng.launches       # kernel launches (<< sum of per-member chunks)

    After :meth:`run` returns, every member policy is a normal
    host-authoritative policy again (stats exact, contents comparable).
    """

    def __init__(self, *, snapshot_every: int | None = None,
                 collect_hits: bool = True):
        if snapshot_every is not None and snapshot_every <= 0:
            raise ValueError("snapshot_every must be positive")
        self.snapshot_every = snapshot_every
        self.collect_hits = collect_hits
        self.members: list[FleetMember] = []
        self.buckets: dict[tuple, _Bucket] = {}
        self.launches = 0  # fleet-kernel launches

    # -- membership ---------------------------------------------------------
    def add(self, policy, keys, sizes, label: str | None = None) -> FleetMember:
        """Enroll one instance with its own trace slice (grid sweeps pass
        the same arrays to every member; sharded deployments pass each
        shard its partition)."""
        m = FleetMember(policy, keys, sizes,
                        label if label is not None else f"m{len(self.members)}")
        self.members.append(m)
        return m

    @classmethod
    def sharded(cls, policies, keys, sizes, *, seed: int = 0, **kw):
        """Model a hash-partitioned deployment: one trace split over
        ``len(policies)`` shard instances via
        :func:`repro.distributed.sharding.hash_partition`."""
        from repro.distributed.sharding import hash_partition

        keys = np.asarray(keys, np.int64)
        sizes = np.asarray(sizes, np.int64)
        shard = hash_partition(keys, len(policies), seed=seed)
        eng = cls(**kw)
        for k, pol in enumerate(policies):
            sel = shard == k
            eng.add(pol, keys[sel], sizes[sel], label=f"shard{k}")
        return eng

    # -- drive --------------------------------------------------------------
    def run(self) -> list[FleetMember]:
        """Drive every member to the end of its trace; returns the members
        (stats live on each member's policy)."""
        if not self.members:
            return self.members
        self._enroll()
        try:
            progressed = True
            while progressed:
                progressed = False
                for b in self.buckets.values():
                    if self._step(b):
                        progressed = True
        finally:
            self._release()
        return self.members

    # -- internals ----------------------------------------------------------
    def _enroll(self) -> None:
        self.buckets = {}
        for m in self.members:
            if m.pipe._fleet_restore is not None:
                raise RuntimeError(
                    f"policy {m.label!r} is already enrolled in a fleet")
        for m in self.members:
            m.pipe._collect(m.policy)  # resolve launches left from prior use
            st = m.pipe._statics(m.policy)
            key = (tuple(sorted(st.items())),
                   tuple(m.pipe.sketch.table.shape))
            b = self.buckets.get(key)
            if b is None:
                b = self.buckets[key] = _Bucket(st)
            m.bucket = b
            m.lane = len(b.members)
            b.members.append(m)
            m.pipe._fleet_restore = functools.partial(self._restore_lane, m)

    def _release(self) -> None:
        for m in self.members:
            try:
                m.pipe.ensure_host(m.policy)
            finally:
                m.pipe._fleet_restore = None
            m.bucket = None
        self.buckets = {}

    def _restore_lane(self, m: FleetMember) -> None:
        """Materialize lane i of the stacked state back into instance i's
        own mirror + sketch table (the plane's download/load_rows path then
        rebuilds the host structures for just this member)."""
        b = m.bucket
        if b is None or b.table is None:
            return  # bucket never launched: host state is still authoritative
        table, main, window = _gather_lane(
            b.table, *b.main, *b.window, m.lane)
        m.pipe.sketch.table = table
        m.pipe.mirror.main = main
        m.pipe.mirror.window = window

    def _member_take(self, m: FleetMember) -> int:
        """How many accesses lane ``m`` contributes to the next launch —
        replaying per-instance aging boundaries through the host path
        first, exactly like the sequential plane's ``drive_chunk`` loop."""
        pipe, pol = m.pipe, m.policy
        sk = pipe.sketch
        end = len(m.keys)
        while m.pos < end:
            if sk._pending:
                sk.flush()
            safe = sk.sample_size - sk._ops - 1
            if safe <= 0:
                pipe.ensure_host(pol)  # restores ONLY this lane
                pipe.resyncs += 1
                pipe.resync_reasons["aging"] += 1
                hit = pol.access(int(m.keys[m.pos]), int(m.sizes[m.pos]))
                self._advance(m, np.asarray([hit], dtype=bool))
                continue
            take = min(end - m.pos, pipe.chunk, safe)
            if self.snapshot_every:
                take = min(take, self.snapshot_every
                           - pol.stats.accesses % self.snapshot_every)
            return take
        return 0

    def _advance(self, m: FleetMember, hits: np.ndarray) -> None:
        if self.collect_hits:
            m.hits.append(hits)
        m.pos += len(hits)
        if not self.snapshot_every:
            return
        st = m.policy.stats
        if st.accesses % self.snapshot_every or st.accesses == m._snap_acc:
            return
        prev = m.snapshots[-1] if m.snapshots else None
        interval = st.accesses - (prev.accesses if prev else 0)
        p_hits = prev.hits if prev else 0
        m.snapshots.append(StatsSnapshot(
            accesses=st.accesses, hits=st.hits,
            bytes_requested=st.bytes_requested, bytes_hit=st.bytes_hit,
            used_bytes=m.policy.used_bytes(), evictions=st.evictions,
            interval_hit_ratio=(st.hits - p_hits) / interval if interval else 0.0,
        ))
        m._snap_acc = st.accesses

    def _ensure_stacks(self, b: _Bucket, uploaded: list[FleetMember]) -> None:
        """Allocate / pad the stacked buffers to the bucket-wide maximum
        logical slot counts, then scatter freshly uploaded lanes in."""
        slots = max([b.slots] + [m.pipe.mirror.slots for m in b.members])
        wslots = max([b.wslots] + [m.pipe.mirror.wslots for m in b.members])
        n = len(b.members)
        if b.table is None:
            rows, width = b.members[0].pipe.sketch.table.shape
            b.table = jnp.zeros((n, rows, width), jnp.int32)
            b.main = [jnp.zeros((n, slots), jnp.int32) for _ in range(5)]
            b.window = [jnp.zeros((n, wslots), jnp.int32) for _ in range(4)]
            b.slots, b.wslots = slots, wslots
        else:
            if slots > b.slots:
                b.main = [jnp.zeros((n, slots), a.dtype).at[:, : b.slots].set(a)
                          for a in b.main]
                b.slots = slots
            if wslots > b.wslots:
                b.window = [
                    jnp.zeros((n, wslots), a.dtype).at[:, : b.wslots].set(a)
                    for a in b.window]
                b.wslots = wslots
        if not uploaded:
            return
        k = len(uploaded)
        idx = np.asarray([m.lane for m in uploaded], np.int32)
        trows = np.stack([np.asarray(m.pipe.sketch.table) for m in uploaded])
        mrows = [np.zeros((k, b.slots), np.int32) for _ in range(5)]
        wrows = [np.zeros((k, b.wslots), np.int32) for _ in range(4)]
        for r, m in enumerate(uploaded):
            for j, arr in enumerate(m.pipe.mirror.main):
                a = np.asarray(arr)
                mrows[j][r, : len(a)] = a
            for j, arr in enumerate(m.pipe.mirror.window):
                a = np.asarray(arr)
                wrows[j][r, : len(a)] = a
        b.table, b.main, b.window = _scatter_lanes(
            b.table, *b.main, *b.window, idx, trows, tuple(mrows),
            tuple(wrows))
        b.main = list(b.main)
        b.window = list(b.window)
        for m in uploaded:
            # the lane is now authoritative; the member's own mirror arrays
            # are shadow copies until ensure_host restores them
            m.pipe._host_auth = False

    def _step(self, b: _Bucket) -> bool:
        takes = [self._member_take(m) for m in b.members]
        if not any(takes):
            return False
        uploaded = []
        for m, t in zip(b.members, takes):
            if not t:
                continue
            if m.pipe._preflight(m.policy, t):
                uploaded.append(m)
        self._ensure_stacks(b, uploaded)

        pad = max(m.pipe.scan_len() for m in b.members)
        inputs = zip(*(
            m.pipe.launch_inputs(m.policy, m.khi[m.pos: m.pos + t],
                                 m.klo[m.pos: m.pos + t],
                                 m.sizes[m.pos: m.pos + t], t, pad)
            for m, t in zip(b.members, takes)))
        outs = _fleet_chunk(
            b.table, *b.main, *b.window,
            *(np.stack(a) for a in inputs), **b.statics)
        self.launches += 1
        # adopt immediately: the stacked inputs were donated
        b.table = outs[0]
        b.main = list(outs[1:6])
        b.window = list(outs[6:10])
        scal_out = np.asarray(outs[10])
        hits_out = np.asarray(outs[12])

        for i, (m, t) in enumerate(zip(b.members, takes)):
            if not t:
                continue
            m.pipe.chunk_calls += 1
            fouts = [None] * 13
            fouts[10] = scal_out[i]
            fouts[12] = hits_out[i]
            m.pipe._inflight = _InFlightSim(
                tuple(fouts), t, m.sizes[m.pos: m.pos + t], m.policy.stats)
            m.pipe._collect(m.policy)  # tick renorm restores only this lane
            self._advance(m, np.asarray(m.pipe._last_hits[:t], dtype=bool))
        return True
