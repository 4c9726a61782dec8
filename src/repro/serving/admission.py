"""Async size-aware admission pipeline for the serving layer.

The paper's pitch is admission at a fraction of the CPU cost of
AdaptSize/LHD/GDSF; the ``device_full`` data plane delivers that by
resolving a whole chunk of cache accesses, admission decisions included,
in one ``lax.scan`` launch. This module closes the remaining gap to the
serving loop: a request waits on an admission verdict only when that
verdict could change what the request sees.

Two hooks implement one protocol:

* :class:`SyncAdmission` — the reference: every lookup-touch and offer is
  a blocking ``policy.access`` call, verdicts are immediate. This is the
  replay baseline the differential suite compares against.
* :class:`AsyncAdmissionPipeline` — the pipeline: cache accesses (lookup
  touches and admission offers, sizes in KV bytes) are *enqueued*; a full
  event chunk drains through ``policy.access_batch`` whose trailing
  launch is left resolving on device (``defer_collect`` on
  :class:`~repro.kernels.device_full.DeviceFullSimulationPlane`) while the
  next chunk fills from live requests — double-buffered launches, with
  verdicts applied lazily under the deferred-visibility contract below.

Laziness never changes observable behaviour. ``PrefixCache`` resolves the
pipeline exactly when a pending verdict could flip what a request sees:

* a lookup that *matches* while offers are pending (a pending admission
  may have evicted the matched entry, or carry a fresher payload);
* a lookup whose block-hash chain intersects a pending candidate's hashes
  (the pending offer may create or deepen the match);
* any stats/state read.

Cold lookups — no match, no hash intersection — are answered immediately
from the serving view without draining the pipeline; those are the common
case under real (Zipf) traffic and what makes the pipeline fast. The
serving-driven column of the differential suite asserts the whole
arrangement is byte-identical to :class:`SyncAdmission` replay.

Event-queue invariant (load-bearing for identity): a touch is only ever
enqueued when the queue holds no pending verdict (matching lookups force a
resolve first), so queued touches always drain as policy hits — exactly
what the synchronous replay sees at the same position in the access
stream. ``PrefixCache`` queues a re-offer of a materialized prefix, made
while no verdict is pending, as a touch for the same reason: it hits, its
verdict is known, and it forces no resolve.
"""

from __future__ import annotations

import time

import numpy as np

__all__ = [
    "AdmissionHook",
    "SyncAdmission",
    "AsyncAdmissionPipeline",
    "make_admission_hook",
]


class AdmissionHook:
    """Protocol between the serving view and the admission policy.

    ``touch``/``offer`` feed the policy's access stream; ``sync`` resolves
    everything and returns the offer verdicts accumulated since the last
    resolve as ``[(key, admitted)]`` in offer order. ``key in hook``
    queries post-resolve policy residency (callers must ``sync`` first
    when exactness matters — :class:`SyncAdmission` is always exact).
    """

    is_async = False

    def touch(self, key: int, size: int) -> None:
        raise NotImplementedError

    def offer(self, key: int, size: int) -> None:
        raise NotImplementedError

    def sync(self) -> list[tuple[int, bool]]:
        raise NotImplementedError

    @property
    def has_pending_offers(self) -> bool:
        return False

    def __contains__(self, key: int) -> bool:
        raise NotImplementedError

    # -- instrumentation (shared shape) -----------------------------------
    def latency_percentiles(self) -> dict:
        """p50/p99 admission-decision latency in milliseconds."""
        lat = self.decision_latencies
        if not lat:
            return {"decision_p50_ms": 0.0, "decision_p99_ms": 0.0}
        arr = np.asarray(lat, dtype=np.float64) * 1e3
        return {
            "decision_p50_ms": round(float(np.percentile(arr, 50)), 6),
            "decision_p99_ms": round(float(np.percentile(arr, 99)), 6),
        }


class SyncAdmission(AdmissionHook):
    """Blocking reference hook: one ``policy.access`` per event, verdict
    returned in line. Decision latency == the access call itself."""

    is_async = False

    def __init__(self, policy, clock=time.perf_counter):
        self.policy = policy
        self._clock = clock
        self.decision_latencies: list[float] = []
        self.events = 0

    def touch(self, key: int, size: int) -> None:
        self.events += 1
        self.policy.access(key, size)

    def offer(self, key: int, size: int) -> bool:
        self.events += 1
        t0 = self._clock()
        self.policy.access(key, size)
        admitted = key in self.policy
        self.decision_latencies.append(self._clock() - t0)
        return admitted

    def sync(self) -> list[tuple[int, bool]]:
        return []

    def __contains__(self, key: int) -> bool:
        return key in self.policy

    def metrics(self) -> dict:
        out = {"mode": "sync", "events": self.events,
               "max_queue_depth": 0, "mean_queue_depth": 0.0}
        out.update(self.latency_percentiles())
        return out


class AsyncAdmissionPipeline(AdmissionHook):
    """Non-blocking hook: events queue up and drain through
    ``policy.access_batch`` in chunks; on the ``device_full`` plane the
    trailing chunk launch stays in flight on device between drains."""

    is_async = True

    def __init__(self, policy, *, queue_chunk: int | None = None,
                 clock=time.perf_counter):
        self.policy = policy
        plane = getattr(policy, "_device_pipeline", None)
        if plane is not None:
            plane.defer_collect = True
        self._plane = plane
        if queue_chunk is None:
            queue_chunk = plane.chunk if plane is not None else 64
        self.queue_chunk = max(1, int(queue_chunk))
        self._clock = clock
        self._keys: list[int] = []
        self._sizes: list[int] = []
        # key -> enqueue time of the oldest unresolved offer for that key
        # (insertion-ordered: verdicts are reported in offer order)
        self._pending_offers: dict[int, float] = {}
        # instrumentation
        self.decision_latencies: list[float] = []
        self.events = 0
        self.pumps = 0
        self.syncs = 0
        self.max_queue_depth = 0
        self._depth_sum = 0
        self._depth_samples = 0

    # -- event intake ------------------------------------------------------
    def _enqueue(self, key: int, size: int) -> None:
        self.events += 1
        self._keys.append(key)
        self._sizes.append(size)
        depth = len(self._keys)
        self._depth_sum += depth
        self._depth_samples += 1
        if depth > self.max_queue_depth:
            self.max_queue_depth = depth
        if depth >= self.queue_chunk:
            self._pump()

    def touch(self, key: int, size: int) -> None:
        self._enqueue(key, size)

    def offer(self, key: int, size: int) -> None:
        """Returns None: the verdict is pending until :meth:`sync`."""
        self._pending_offers.setdefault(key, self._clock())
        self._enqueue(key, size)
        return None

    @property
    def has_pending_offers(self) -> bool:
        return bool(self._pending_offers)

    @property
    def queue_depth(self) -> int:
        return len(self._keys)

    # -- draining ----------------------------------------------------------
    def _pump(self) -> None:
        """Drain the event queue into the policy. Under ``defer_collect``
        the policy's trailing chunk launch dispatches without blocking —
        it resolves on device while new events gather here."""
        if not self._keys:
            return
        self.pumps += 1
        # plain lists: serving keys are full 64-bit block hashes, which
        # overflow an int64 array; access_batch accepts sequences
        keys, self._keys = self._keys, []
        sizes, self._sizes = self._sizes, []
        batch = getattr(self.policy, "access_batch", None)
        if batch is not None:
            batch(keys, sizes)
        else:
            for k, s in zip(keys, sizes):
                self.policy.access(k, s)

    def sync(self) -> list[tuple[int, bool]]:
        """Drain everything, collect any in-flight device chunk, and
        return the accumulated offer verdicts in offer order."""
        self.syncs += 1
        self._pump()
        if self._plane is not None:
            # verdicts and stats need the launch, not host authority:
            # membership is answered from the device's resident keys
            self._plane.collect(self.policy)
        if not self._pending_offers:
            return []
        now = self._clock()
        verdicts = []
        for key, t0 in self._pending_offers.items():
            self.decision_latencies.append(now - t0)
            verdicts.append((key, key in self.policy))
        self._pending_offers.clear()
        return verdicts

    def __contains__(self, key: int) -> bool:
        return key in self.policy

    def metrics(self) -> dict:
        out = {
            "mode": "async",
            "events": self.events,
            "pumps": self.pumps,
            "syncs": self.syncs,
            "max_queue_depth": self.max_queue_depth,
            "mean_queue_depth": round(
                self._depth_sum / self._depth_samples, 3)
            if self._depth_samples else 0.0,
        }
        out.update(self.latency_percentiles())
        if self._plane is not None:
            out["deferred_dispatches"] = self._plane.deferred_dispatches
            out["chunk_calls"] = self._plane.chunk_calls
            out["decisions"] = self._plane.decisions
        return out


def make_admission_hook(policy, mode: str = "sync", *,
                        queue_chunk: int | None = None) -> AdmissionHook:
    """Build an admission hook over ``policy``. ``mode``: "sync" | "async"."""
    if mode == "sync":
        return SyncAdmission(policy)
    if mode == "async":
        return AsyncAdmissionPipeline(policy, queue_chunk=queue_chunk)
    raise ValueError(f"unknown admission mode: {mode!r} (want sync|async)")
