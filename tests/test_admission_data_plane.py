"""The admission control-plane/data-plane split (ISSUE 2 acceptance).

Covers, layer by layer:

* ``EvictionPolicy.peek_victims`` ≡ gathering ``iter_victims`` until the
  victim sizes cover ``needed`` — for every eviction policy, including the
  sampling ones (whose counter-based RNG makes peeking a replay), both as
  seeded sweeps and hypothesis properties;
* batched vs scalar admission planes produce **byte-identical** hit/miss
  decision streams, ``CacheStats`` and final cache contents, trace-wide,
  across every ``TRACE_SPECS`` class and every admission x eviction combo;
* the batched plane issues exactly ONE ``estimate_batch`` call per
  admission decision and zero scalar ``estimate`` calls on the hot path;
* ``CMSSketch.estimate_batch``'s fused flush+score kernel path equals the
  staged flush-then-estimate path;
* the per-decision device kernel (``device_full``'s host path, driven
  through scalar ``access``): exactly ONE jitted decision call per
  admission decision (no per-victim host round-trips), the
  incrementally-maintained device key/size mirror staying in sync with the
  eviction policy, three-way scalar == batched == device_full
  byte-identity (the exhaustive grid lives in
  ``tests/test_property_differential.py``), and the retired plane names
  refused.
"""

import random

import numpy as np
import pytest
from _hypothesis_compat import HealthCheck, given, settings, st

from repro.core import (
    REGISTRY,
    HitMaskRecorder,
    SimulationEngine,
    SizeAwareWTinyLFU,
    make_admission,
)
from repro.core.eviction import make_eviction
from repro.traces import TRACE_SPECS, make_trace

EVICTIONS = (
    "lru",
    "slru",
    "sampled_frequency",
    "sampled_size",
    "sampled_frequency_size",
    "sampled_needed_size",
    "random",
)


def _gather_iter(e, needed):
    """Reference: drain iter_victims until the sizes cover ``needed``."""
    keys, sizes, total = [], [], 0
    if needed > 0:
        for v in e.iter_victims(needed):
            keys.append(v)
            s = e.sizes[v]
            sizes.append(s)
            total += s
            if total >= needed:
                break
    return keys, sizes


def _check_peek_equivalence(e, needed):
    """peek_victims must equal the iter_victims gather, must not mutate the
    policy, and must replay (counter-based RNG: peeking consumes nothing)."""
    ref_keys, ref_sizes = _gather_iter(e, needed)
    before = (len(e), e.used)
    keys, sizes = e.peek_victims(needed)
    assert isinstance(keys, np.ndarray) and isinstance(sizes, np.ndarray)
    assert keys.dtype == np.int64 and sizes.dtype == np.int64
    assert keys.tolist() == ref_keys
    assert sizes.tolist() == ref_sizes
    assert (len(e), e.used) == before, "peek_victims mutated the policy"
    keys2, _ = e.peek_victims(needed)
    assert keys2.tolist() == ref_keys, "peeking twice must replay identically"


def _filled_eviction(name, entries, *, hot_accesses=()):
    e = make_eviction(name, capacity=10**9, freq_fn=lambda k: (k * 7) % 13, seed=0xA11CE)
    for k, s in entries:
        e.insert(k, s)
    for k in hot_accesses:
        e.on_access(k)
    return e


def test_auto_data_plane_resolves_per_backend():
    """auto -> scalar walk on the host sketch, batched on the CMS kernels."""
    host = SizeAwareWTinyLFU(10_000, expected_entries=64)
    assert host.data_plane == "scalar"
    cms = SizeAwareWTinyLFU(10_000, expected_entries=64, sketch_backend="cms")
    assert cms.data_plane == "batched"
    pinned = SizeAwareWTinyLFU(10_000, expected_entries=64, data_plane="batched")
    assert pinned.data_plane == "batched"
    with pytest.raises(ValueError, match="data_plane"):
        SizeAwareWTinyLFU(10_000, expected_entries=64, data_plane="bogus")


def test_make_admission_validates_name():
    from repro.core.sketch import FrequencySketch

    sk = FrequencySketch(64)
    assert make_admission("iv", sk).name == "iv"
    assert make_admission("AV", sk, early_pruning=False).early_pruning is False
    with pytest.raises(ValueError, match="admission"):
        make_admission("bogus", sk)


class TestPeekVictims:
    @pytest.mark.parametrize("name", EVICTIONS)
    def test_matches_iter_victims_seeded_sweep(self, name):
        rnd = random.Random(7)
        for trial in range(30):
            n = rnd.randint(1, 50)
            entries = [(k, rnd.randint(1, 400)) for k in rnd.sample(range(10_000), n)]
            hot = [k for k, _ in entries if rnd.random() < 0.3]
            e = _filled_eviction(name, entries, hot_accesses=hot)
            total = sum(s for _, s in entries)
            for needed in (0, 1, rnd.randint(1, max(1, total)), total, total + 123):
                _check_peek_equivalence(e, needed)

    @pytest.mark.parametrize("name", EVICTIONS)
    @settings(max_examples=25, deadline=None, suppress_health_check=(HealthCheck.too_slow,))
    @given(
        entries=st.lists(
            st.tuples(st.integers(0, 10_000), st.integers(1, 400)),
            min_size=1,
            max_size=40,
            unique_by=lambda kv: kv[0],
        ),
        needed_frac=st.floats(0.0, 1.5),
    )
    def test_matches_iter_victims_property(self, name, entries, needed_frac):
        e = _filled_eviction(name, entries)
        needed = int(sum(s for _, s in entries) * needed_frac)
        _check_peek_equivalence(e, needed)

    def test_empty_and_nonpositive_needed(self):
        for name in EVICTIONS:
            e = _filled_eviction(name, [(1, 10)])
            for needed in (0, -5):
                keys, sizes = e.peek_victims(needed)
                assert len(keys) == 0 and len(sizes) == 0

    def test_peek_stability_flags(self):
        """Counter-based RNG makes EVERY built-in eviction peek-stable
        (the sampled policies' draws are pure functions of the decision
        counter), so the batched admission plane never falls back."""
        for name in EVICTIONS:
            assert _filled_eviction(name, [(1, 1)]).peek_stable, name

    def test_decision_counter_advances_stream(self):
        """begin_decision() — and only it — moves the sampled victim
        stream; walks replay until the caller commits a new decision."""
        e = _filled_eviction("sampled_frequency", [(k, 10) for k in range(30)])
        first = list(e.iter_victims(0))[:5]
        assert list(e.iter_victims(0))[:5] == first  # replays
        e.begin_decision()
        shifted = list(e.iter_victims(0))[:5]
        assert shifted != first  # fresh stream (30 keys: collision ~ never)
        assert list(e.iter_victims(0))[:5] == shifted


def _run_both_planes(spec, tr, cap, **kw):
    out = []
    for plane in ("scalar", "batched"):
        p = REGISTRY.build(spec, cap, data_plane=plane, **kw)
        rec = HitMaskRecorder()
        SimulationEngine(instruments=(rec,)).run(p, tr)
        out.append((p, rec.hits))
    return out


def _assert_byte_identical(a, b, hits_a, hits_b, label=""):
    for p in (a, b):
        p.sync_deferred()  # device_full: restore host authority first
    assert np.array_equal(hits_a, hits_b), f"{label}: hit/miss streams diverge"
    sa, sb = a.stats, b.stats
    for field in ("accesses", "hits", "bytes_hit", "victims_examined",
                  "admissions", "rejections", "evictions"):
        assert getattr(sa, field) == getattr(sb, field), f"{label}: stats.{field}"
    assert list(a.window.items()) == list(b.window.items()), f"{label}: window"
    assert a.main.sizes == b.main.sizes, f"{label}: main contents"
    assert a.used_bytes() == b.used_bytes(), f"{label}: used bytes"


class TestBatchedScalarEquivalence:
    @pytest.mark.parametrize("trace_name", sorted(TRACE_SPECS))
    def test_every_trace_class(self, trace_name):
        """Acceptance: byte-identical decisions + CacheStats on every
        TRACE_SPECS class (default wtlfu-av-slru)."""
        tr = make_trace(trace_name, seed=11, scale=0.002)
        cap = max(1, int(tr.total_object_bytes * 0.02))
        kw = dict(expected_entries=max(64, int(cap / tr.mean_object_size)))
        (a, ha), (b, hb) = _run_both_planes("wtlfu-av", tr, cap, **kw)
        assert not a.stats.hits == 0 or len(tr) < 100  # sanity: trace exercised
        _assert_byte_identical(a, b, ha, hb, trace_name)

    @pytest.mark.parametrize("admission", ("iv", "qv", "av"))
    @pytest.mark.parametrize("eviction", EVICTIONS)
    def test_every_admission_eviction_combo(self, admission, eviction):
        tr = make_trace("msr2", seed=5, scale=0.003)
        cap = max(1, int(tr.total_object_bytes * 0.02))
        spec = f"wtlfu-{admission}-{eviction}"
        kw = dict(expected_entries=max(64, int(cap / tr.mean_object_size)))
        (a, ha), (b, hb) = _run_both_planes(spec, tr, cap, **kw)
        _assert_byte_identical(a, b, ha, hb, spec)

    @pytest.mark.parametrize("spec", ("wtlfu-av?early_pruning=0", "wtlfu-av?early_pruning=0&eviction=random"))
    def test_av_without_pruning(self, spec):
        tr = make_trace("cdn1", seed=5, scale=0.002)
        cap = max(1, int(tr.total_object_bytes * 0.05))
        (a, ha), (b, hb) = _run_both_planes(spec, tr, cap, expected_entries=256)
        _assert_byte_identical(a, b, ha, hb, spec)

    @settings(max_examples=20, deadline=None, suppress_health_check=(HealthCheck.too_slow,))
    @given(
        keys=st.lists(st.integers(0, 40), min_size=30, max_size=300),
        admission=st.sampled_from(("iv", "qv", "av")),
        eviction=st.sampled_from(EVICTIONS),
    )
    def test_property_random_streams(self, keys, admission, eviction):
        """Property: the planes agree on arbitrary small access streams."""
        sizes = [(k * 37) % 90 + 10 for k in keys]
        tr = list(zip(keys, sizes))
        planes = []
        for plane in ("scalar", "batched"):
            p = SizeAwareWTinyLFU(
                300, admission=admission, eviction=eviction,
                window_frac=0.1, expected_entries=64, data_plane=plane,
            )
            hits = [p.access(k, s) for k, s in tr]
            planes.append((p, np.asarray(hits)))
        (a, ha), (b, hb) = planes
        _assert_byte_identical(a, b, ha, hb, f"{admission}/{eviction}")


class TestOneBatchedCallPerDecision:
    @pytest.mark.parametrize("admission", ("iv", "qv", "av"))
    def test_no_scalar_estimates_on_hot_path(self, admission):
        """Acceptance: one estimate_batch call per admission decision, zero
        per-victim Python estimate calls (default SLRU main)."""
        tr = make_trace("msr2", seed=9, scale=0.003)
        cap = max(1, int(tr.total_object_bytes * 0.02))
        p = SizeAwareWTinyLFU(
            cap, admission=admission, data_plane="batched",
            expected_entries=max(64, int(cap / tr.mean_object_size)),
        )
        counts = {"batch": 0, "scalar": 0, "decisions": 0}
        sk = p.sketch
        orig_estimate = sk.estimate

        def spy_estimate(key):
            counts["scalar"] += 1
            return orig_estimate(key)

        def spy_batch(keys):
            counts["batch"] += 1
            return [orig_estimate(int(k)) for k in keys]

        sk.estimate = spy_estimate
        sk.estimate_batch = spy_batch
        p.admission_policy.estimate_batch = spy_batch  # rebind data-plane hook
        orig_admit = p._admit

        def spy_admit(*args):
            counts["decisions"] += 1
            return orig_admit(*args)

        p._admit = spy_admit

        SimulationEngine().run(p, tr)
        assert counts["decisions"] > 50, "trace too small to be meaningful"
        assert counts["batch"] == counts["decisions"]
        assert counts["scalar"] == 0


class TestBatchedNeverFallsBack:
    """ISSUE 3 acceptance: data_plane="batched" actually RUNS the batched
    plane (no admit_scalar fallback) for the four sampled evictions and
    Random, across all admission policies."""

    @pytest.mark.parametrize("admission", ("iv", "qv", "av"))
    @pytest.mark.parametrize("eviction", EVICTIONS[2:])
    def test_no_admit_scalar_under_batched_plane(self, admission, eviction):
        tr = make_trace("msr2", seed=5, scale=0.0015)
        cap = max(1, int(tr.total_object_bytes * 0.02))
        p = SizeAwareWTinyLFU(
            cap, admission=admission, eviction=eviction, data_plane="batched",
            expected_entries=max(64, int(cap / tr.mean_object_size)),
        )
        counts = {"batched": 0, "scalar": 0}
        orig_admit = p.admission_policy.admit
        orig_scalar = p.admission_policy.admit_scalar

        def spy_admit(*args):
            counts["batched"] += 1
            return orig_admit(*args)

        def spy_scalar(*args):
            counts["scalar"] += 1
            return orig_scalar(*args)

        p._admit = spy_admit
        p.admission_policy.admit_scalar = spy_scalar
        SimulationEngine().run(p, tr)
        assert counts["batched"] > 20, "trace too small to be meaningful"
        assert counts["scalar"] == 0, f"{admission}/{eviction} fell back"


class TestDevicePlane:
    """The per-decision device kernel: ``device_full``'s host path, driven
    through scalar ``access``."""

    @pytest.mark.parametrize("plane", ("device", "device_batched"))
    def test_retired_planes_refused(self, plane):
        """``device_full`` is the one device plane: the retired names fail
        the constructor's validation, spec-built or not, naming the valid
        planes."""
        from repro.core.tinylfu import DATA_PLANES

        assert DATA_PLANES == ("auto", "batched", "scalar", "device_full")
        with pytest.raises(ValueError, match="device_full"):
            SizeAwareWTinyLFU(10_000, expected_entries=64, data_plane=plane)
        with pytest.raises(ValueError, match="data_plane"):
            REGISTRY.build(f"wtlfu-av-random?data_plane={plane}&seed=0x5EED",
                           5_000, expected_entries=64)

    @pytest.mark.parametrize("eviction", ("sampled_frequency", "slru"))
    def test_one_jitted_call_per_decision(self, eviction):
        """Acceptance: at most one jitted device call per admission
        decision — and with no aging reset due, exactly one (zero staged
        flushes), for both the mirror walk and the prefix kernel. Scalar
        drive pins the per-decision contract (``access_batch`` resolves
        whole chunks in the scan instead — see ``TestDeviceFullPlane``)."""
        tr = make_trace("msr2", seed=9, scale=0.0008)
        cap = max(1, int(tr.total_object_bytes * 0.02))
        p = SizeAwareWTinyLFU(
            cap, admission="av", eviction=eviction, data_plane="device_full",
            expected_entries=max(64, int(cap / tr.mean_object_size)),
            sketch_kwargs={"sample_factor": 10_000},  # no resets this trace
        )
        counts = {"decisions": 0}
        orig_admit = p._admit

        def spy_admit(*args):
            counts["decisions"] += 1
            return orig_admit(*args)

        p._admit = spy_admit
        SimulationEngine(use_batch=False).run(p, tr)
        plane = p.admission_policy._device
        assert counts["decisions"] > 20, "trace too small to be meaningful"
        assert plane.calls == counts["decisions"]
        assert plane.staged_flushes == 0

    def test_staged_flush_only_at_reset_boundaries(self):
        """Pending batches that straddle an aging reset take the staged
        path (the only case allowed to add a device call); the sketch ops
        counter must keep matching scalar driving."""
        tr = make_trace("msr2", seed=9, scale=0.0008)
        cap = max(1, int(tr.total_object_bytes * 0.02))
        p = SizeAwareWTinyLFU(cap, data_plane="device_full", expected_entries=16,
                              eviction="sampled_frequency")
        SimulationEngine(use_batch=False).run(p, tr)
        plane = p.admission_policy._device
        assert p.sketch.resets > 0, "sketch never aged; shrink expected_entries"
        assert plane.staged_flushes > 0
        assert plane.staged_flushes <= p.sketch.resets + 1
        assert p.sketch._ops < p.sketch.sample_size

    def test_mirror_tracks_eviction_policy(self):
        """The device mirror is maintained incrementally by the insert/evict
        hooks: after an arbitrary run it matches the policy's slot table
        without having been re-uploaded per decision."""
        tr = make_trace("cdn1", seed=3, scale=0.0008)
        cap = max(1, int(tr.total_object_bytes * 0.02))
        p = SizeAwareWTinyLFU(cap, admission="qv", eviction="sampled_size",
                              data_plane="device_full",
                              expected_entries=max(64, int(cap / tr.mean_object_size)))
        SimulationEngine(use_batch=False).run(p, tr)
        plane = p.admission_policy._device
        assert plane.calls > 20
        n = len(p.main.keys)
        mirror_keys = plane.mirror._keys[:n].tolist()
        mirror_sizes = plane.mirror._sizes[:n].tolist()
        assert mirror_keys == [k & 0xFFFFFFFF for k in p.main.keys]
        assert mirror_sizes == [p.main.sizes[k] for k in p.main.keys]
        # incremental maintenance: a handful of full uploads (first use +
        # growth doublings), not one per decision
        assert plane.mirror.uploads < plane.calls / 4

    @pytest.mark.parametrize("admission", ("iv", "qv", "av"))
    def test_three_way_trace_equivalence(self, admission):
        """Spot three-way check on an engine-driven trace, device_full
        through ``access_batch`` (the exhaustive 21-combo grid runs in
        tests/test_property_differential.py)."""
        tr = make_trace("msr2", seed=5, scale=0.0008)
        cap = max(1, int(tr.total_object_bytes * 0.02))
        kw = dict(expected_entries=max(64, int(cap / tr.mean_object_size)),
                  sketch_backend="cms")
        spec = f"wtlfu-{admission}-sampled_frequency_size"
        out = []
        for plane in ("scalar", "batched", "device_full"):
            p = REGISTRY.build(spec, cap, data_plane=plane, **kw)
            rec = HitMaskRecorder()
            SimulationEngine(instruments=(rec,)).run(p, tr)
            out.append((p, rec.hits))
        (a, ha), (b, hb), (c, hc) = out
        _assert_byte_identical(a, b, ha, hb, f"{spec} scalar-vs-batched")
        _assert_byte_identical(a, c, ha, hc, f"{spec} scalar-vs-device_full")


def _drive_batched(p, keys, sizes, step=37):
    """Drive via access_batch in uneven chunks (exercises buffer flushes
    landing mid-chunk and at chunk boundaries)."""
    hits = []
    ka = np.asarray(keys, dtype=np.int64)
    sa = np.asarray(sizes, dtype=np.int64)
    for lo in range(0, len(ka), step):
        hits.extend(p.access_batch(ka[lo : lo + step], sa[lo : lo + step]).tolist())
    return hits


def _assert_mirror_synced(p, label=""):
    """The device key/size twin must match the eviction policy's slot table
    byte-for-byte: host copy AND the device-resident arrays overlaid with
    the not-yet-scattered dirty slots."""
    mirror = p.admission_policy._device.mirror
    main = p.main
    n = len(main.keys)
    want_keys = [k & 0xFFFFFFFF for k in main.keys]
    want_sizes = [main.sizes[k] for k in main.keys]
    assert mirror._keys[:n].tolist() == want_keys, f"{label}: host mirror keys"
    assert mirror._sizes[:n].tolist() == want_sizes, f"{label}: host mirror sizes"
    if mirror._dev is not None:
        dev_keys = np.asarray(mirror._dev[0]).astype(np.int64) & 0xFFFFFFFF
        dev_sizes = np.asarray(mirror._dev[1]).astype(np.int64)
        for slot in mirror._dirty:  # pending scatter: next decision's writes
            dev_keys[slot] = mirror._keys[slot]
            dev_sizes[slot] = mirror._sizes[slot]
        assert dev_keys[:n].tolist() == want_keys, f"{label}: device mirror keys"
        assert dev_sizes[:n].tolist() == want_sizes, f"{label}: device mirror sizes"


class TestDeviceBatchedPlane:
    """Chunked and per-access drives of ``device_full`` against the scalar
    plane: engine-driven identity, snapshot alignment, the aging and
    mirror-growth resyncs, and the per-decision kernel's key/size mirror."""

    def _trace(self, seed=5, scale=0.0015):
        tr = make_trace("msr2", seed=seed, scale=scale)
        cap = max(1, int(tr.total_object_bytes * 0.02))
        return tr, cap, max(64, int(cap / tr.mean_object_size))

    def test_scalar_access_resolves_per_decision(self):
        """Scalar ``access()`` on device_full (also the adaptive-window
        drain's path) resolves each decision through the per-decision
        kernel, byte-identical to the scalar plane, without launching the
        chunk program."""
        rng = np.random.default_rng(17)
        keys = ((rng.zipf(1.25, size=400) - 1) % 35).astype(np.int64).tolist()
        sizes = [10 + (k * 11) % 80 for k in keys]
        spec = "wtlfu-av-sampled_frequency?sketch_backend=cms&adaptive_window=1"
        a = REGISTRY.build(spec, 600, data_plane="scalar", expected_entries=64)
        ha = [a.access(k, s) for k, s in zip(keys, sizes)]
        d = REGISTRY.build(spec, 600, data_plane="device_full", expected_entries=64)
        hd = [d.access(k, s) for k, s in zip(keys, sizes)]
        _assert_byte_identical(a, d, np.asarray(ha), np.asarray(hd), "scalar access")
        assert d._device_pipeline.chunk_calls == 0  # the scan is chunk-path only
        assert d.admission_policy._device.calls > 0

    @pytest.mark.parametrize("admission,eviction",
                             [("iv", "sampled_size"), ("qv", "sampled_frequency"),
                              ("av", "random"), ("av", "slru")])
    def test_engine_driven_byte_identity(self, admission, eviction):
        """Engine-driven device_full == scalar-driven scalar plane:
        decisions, CacheStats, contents, fallback counters."""
        tr, cap, ee = self._trace(scale=0.0008)
        out = []
        for plane, use_batch in (("scalar", False), ("device_full", "auto")):
            p = REGISTRY.build(
                f"wtlfu-{admission}-{eviction}?sketch_backend=cms", cap,
                data_plane=plane, expected_entries=ee, chunk=8)
            rec = HitMaskRecorder()
            SimulationEngine(instruments=(rec,), use_batch=use_batch).run(p, tr)
            out.append((p, rec.hits))
        (a, ha), (b, hb) = out
        _assert_byte_identical(a, b, ha, hb, f"{admission}/{eviction} device_full")
        if eviction not in ("lru", "slru"):
            assert a.main.fallback_scans == b.main.fallback_scans
            _assert_mirror_synced(b, f"{admission}/{eviction}")

    def test_warmup_snapshot_alignment_with_buffered_decisions(self):
        """access_batch leaves stats exact when it returns, so engine
        snapshots land exactly ``snapshot_every`` accesses after warmup
        even when warmup ends mid-chunk."""
        tr, cap, ee = self._trace(scale=0.0008)
        n = len(tr)
        warmup, every = 137, 250
        p = SizeAwareWTinyLFU(cap, data_plane="device_full",
                              eviction="sampled_frequency", expected_entries=ee)
        res = SimulationEngine(chunk_size=100, warmup=warmup,
                               snapshot_every=every).run(p, tr)
        got = [s.accesses for s in res.snapshots]
        assert got == [every * (i + 1) for i in range((n - warmup) // every)]

    def test_aging_reset_mid_chunk_resyncs_and_matches(self):
        """A tiny sketch forces aging resets inside chunks: the plane must
        replay the boundary access through the per-decision staged path
        (counted in resync_reasons['aging']) and stay byte-identical —
        same resets, same ops counter, same decisions."""
        tr, cap, ee = self._trace(scale=0.0008)
        out = []
        for plane in ("scalar", "device_full"):
            p = REGISTRY.build("wtlfu-qv-sampled_frequency?sketch_backend=cms",
                               cap, data_plane=plane, expected_entries=16, chunk=8)
            rec = HitMaskRecorder()
            SimulationEngine(instruments=(rec,)).run(p, tr)
            out.append((p, rec.hits))
        (a, ha), (b, hb) = out
        b.sync_deferred()
        assert a.sketch.resets > 0, "sketch never aged; shrink expected_entries"
        assert a.sketch.resets == b.sketch.resets
        assert a.sketch._ops == b.sketch._ops
        pipe = b._device_pipeline
        assert pipe.resync_reasons["aging"] > 0, "aging resync never exercised"
        _assert_byte_identical(a, b, ha, hb, "aging resync")

    def test_mirror_overflow_mid_chunk_grows_and_matches(self):
        """Entry growth past the device slot arrays mid-run: the launch
        pre-flight grows them on device (resync_reasons['mirror_grow']) so
        no in-scan insert can land past the arrays; contents stay
        byte-identical and the per-decision twin stays in sync."""
        rng = np.random.default_rng(5)
        ks = 800
        keys = ((rng.zipf(1.25, size=2500) - 1) % ks).astype(np.int64)
        sizes = np.minimum(rng.integers(8, 40, size=ks)[keys], 20).astype(np.int64)
        cap = 20 * 400  # ~400 small entries: well past the 64-slot first upload
        out = []
        for plane in ("scalar", "device_full"):
            p = REGISTRY.build("wtlfu-qv-sampled_size?seed=9&sketch_backend=cms",
                               cap, data_plane=plane, expected_entries=256, chunk=16)
            hits = _drive_batched(p, keys, sizes, step=53)
            p.sync_deferred()
            out.append((p, hits))
        (a, ha), (b, hb) = out
        assert ha == hb and a.main.sizes == b.main.sizes
        pipe = b._device_pipeline
        assert len(b.main.keys) > 128
        assert pipe.resync_reasons["mirror_grow"] > 0, "mirror growth never exercised"
        _assert_mirror_synced(b, "mirror growth")

    # -- DeviceMirror stale-slot regression ----------------------------------

    def test_mirror_stale_slot_same_decision_backfill_chain(self):
        """Evicting multiple victims in one decision chains swap-removes:
        a victim sitting in the back-fill (last) slot must be re-addressed
        after earlier evictions move it. The device twin must match the
        host eviction state byte-for-byte after every decision."""
        p = SizeAwareWTinyLFU(
            600, admission="av", eviction="sampled_size",
            data_plane="device_full", window_frac=0.05, expected_entries=64,
            sketch_kwargs={"sample_factor": 10_000})
        rnd = random.Random(0xBEEF)
        for i in range(600):
            key = rnd.randrange(60)
            p.access(key, 20 + (key * 13) % 90)  # multi-victim decisions
            _assert_mirror_synced(p, f"access {i}")

    def test_mirror_slot_reuse_across_decision_boundary(self):
        """Evict-then-reinsert of the same key across a decision boundary
        reuses freed slots: the twin must track the reused slot's new
        (key, size), not the stale tenant — under per-access drive and
        after chunked drives."""
        for drive in ("access", "access_batch"):
            p = SizeAwareWTinyLFU(
                400, admission="qv", eviction="sampled_frequency",
                data_plane="device_full", window_frac=0.1, expected_entries=64)
            rnd = random.Random(7)
            keys = [rnd.randrange(25) for _ in range(500)]
            sizes = [15 + (k * 7) % 60 for k in keys]
            if drive == "access":
                for i, (k, s) in enumerate(zip(keys, sizes)):
                    p.access(k, s)
                    _assert_mirror_synced(p, f"{drive} access {i}")
            else:
                for lo in range(0, len(keys), 31):
                    _drive_batched(p, keys[lo : lo + 31], sizes[lo : lo + 31], step=31)
                    p.sync_deferred()
                    _assert_mirror_synced(p, f"{drive} chunk at {lo}")


class TestDeviceFullPlane:
    """ISSUE 7: the whole-simulation-on-device plane — one ``lax.scan``
    launch per chunk resolves window hits, recency updates, the miss
    cascade, and the adaptive climber with the cache state device-resident
    between launches (byte-identity lives in the five-way differential
    suite; this class covers the plane mechanics: residency, donation
    adoption, host-sync guards, and the serving defer surface)."""

    def _trace(self, seed=5, scale=0.0015):
        tr = make_trace("msr2", seed=seed, scale=scale)
        cap = max(1, int(tr.total_object_bytes * 0.02))
        return tr, cap, max(64, int(cap / tr.mean_object_size))

    def test_plane_resolution_and_spec_round_trip(self):
        from repro.core import PolicySpec

        spec = PolicySpec.parse(
            "wtlfu-av-slru?data_plane=device_full&chunk=32&seed=0xA11CE")
        assert PolicySpec.parse(spec.to_string()) == spec
        p = REGISTRY.build(spec, 10_000, expected_entries=64)
        assert p.data_plane == "device_full"
        assert p.sketch_backend == "cms"  # implied, like the other device planes
        assert p._device_pipeline.chunk == 32
        assert p._device_pipeline.main_kind == "slru"
        with pytest.raises(ValueError, match="cms"):
            SizeAwareWTinyLFU(10_000, expected_entries=64,
                              data_plane="device_full", sketch_backend="host")
        with pytest.raises(ValueError, match="chunk"):
            SizeAwareWTinyLFU(10_000, expected_entries=64,
                              data_plane="device_full", chunk=0)

    def test_one_launch_per_chunk_device_resident(self):
        """Acceptance: a steady-state run resolves every access — window
        hits and LRU/SLRU main hits included — in exactly one launch per
        chunk, with ONE host->device upload for the whole run, zero
        per-decision kernel dispatches, and zero resyncs."""
        rng = np.random.default_rng(23)
        n = 1280
        keys = ((rng.zipf(1.2, size=n) - 1) % 40).astype(np.int64)
        sizes = np.asarray([10 + (int(k) * 11) % 60 for k in keys], np.int64)
        for eviction, kind in (("lru", "lru"), ("slru", "slru"),
                               ("sampled_frequency", "sampled")):
            p = REGISTRY.build(
                f"wtlfu-av-{eviction}?data_plane=device_full&chunk=64",
                900, expected_entries=256)
            pipe = p._device_pipeline
            assert pipe.main_kind == kind
            # warm up: the first launches size the mirror from an empty
            # cache and may grow it once as the live set fills
            for lo in range(0, 256, 64):
                p.access_batch(keys[lo:lo + 64], sizes[lo:lo + 64])
            p.sync_deferred()  # re-upload next launch with settled sizes
            uploads0, calls0 = pipe.uploads, pipe.chunk_calls
            resyncs0 = pipe.resyncs
            for lo in range(256, n, 64):
                p.access_batch(keys[lo:lo + 64], sizes[lo:lo + 64])
            assert pipe.chunk_calls - calls0 == (n - 256) // 64, eviction
            assert pipe.uploads == uploads0 + 1, \
                f"{eviction}: host re-upload mid-steady-state"
            assert pipe.resyncs == resyncs0, eviction
            assert pipe.decisions > 0, eviction
            # zero per-decision host round-trips: the per-decision device
            # plane (the resync path) never dispatched
            assert p.admission_policy._device.calls == 0, eviction
            assert p.stats.hits > 0, f"{eviction}: hit path never exercised"

    def test_mirror_grow_bounded_across_aging_resyncs(self):
        """ISSUE 8 satellite (failing before): every aging resync marks
        the mirror stale, and the re-upload used to size the slot arrays
        back DOWN to the live set — so a workload whose live-entry count
        swings across a power-of-two boundary re-triggered ``mirror_grow``
        every aging cycle. The high-water floor keeps re-uploads at the
        largest size ever provisioned: grows happen only while the
        high-water mark is still being established, bounded for the whole
        run instead of per cycle."""
        p = REGISTRY.build(
            "wtlfu-qv-sampled_frequency?data_plane=device_full&chunk=8",
            300, expected_entries=16)
        pipe = p._device_pipeline
        # phases alternate tiny and large objects: the live count swings
        # between ~300 entries (needs 512 slots) and ~6 (fits the 64
        # minimum), with the small sketch sample forcing frequent aging
        keys = np.arange(8 * 400, dtype=np.int64)
        sizes = np.concatenate([
            np.full(400, 1 if ph % 2 == 0 else 50, np.int64)
            for ph in range(8)])
        for lo in range(0, len(keys), 64):
            p.access_batch(keys[lo:lo + 64], sizes[lo:lo + 64])
        p.sync_deferred()
        assert pipe.resync_reasons["aging"] >= 20, \
            "aging churn never materialized — the scenario is inert"
        assert pipe.resync_reasons["mirror_grow"] <= 3, (
            "mirror_grow thrash: re-uploads are shrinking the mirror "
            f"below its high-water mark ({dict(pipe.resync_reasons)})")
        # the floor itself persisted through every shrink-phase re-upload
        assert pipe.mirror.slots == pipe.mirror.hiwater == 512

    def test_donated_buffers_adopted_identity(self):
        """ISSUE 7 satellite: the scan entry point donates the packed
        state buffers, and the plane adopts the launch outputs immediately
        — the sketch table and every mirror array the plane holds must BE
        the launch's output objects (no host copy, no re-allocation)."""
        from repro.kernels import device_full as df

        recorded = []
        real = df._simulate_chunk

        def recording(*args, **kw):
            outs = real(*args, **kw)
            recorded.append(outs)
            return outs

        p = REGISTRY.build(
            "wtlfu-qv-sampled_frequency?data_plane=device_full&chunk=32",
            800, expected_entries=64)
        rng = np.random.default_rng(3)
        keys = ((rng.zipf(1.3, size=96) - 1) % 30).astype(np.int64)
        sizes = np.asarray([12 + (int(k) * 7) % 50 for k in keys], np.int64)
        try:
            df._simulate_chunk = recording
            p.access_batch(keys, sizes)
        finally:
            df._simulate_chunk = real
        assert recorded, "simulation kernel never launched"
        outs = recorded[-1]
        pipe = p._device_pipeline
        assert p.sketch.table is outs[0], "sketch table was copied, not adopted"
        for got, want in zip(pipe.mirror.main, outs[1:6]):
            assert got is want, "mirror main array was copied, not adopted"
        for got, want in zip(pipe.mirror.window, outs[6:10]):
            assert got is want, "mirror window array was copied, not adopted"

    @pytest.mark.parametrize("drive", ["sequential", "fleet"])
    def test_capacity_past_int32_refused(self, drive):
        """The packed scalar vector is int32: a 4 GiB capacity used to wrap
        silently into a negative capacity. Both drives must refuse it."""
        from repro.kernels.fleet import FleetEngine

        p = REGISTRY.build("wtlfu-av-slru?data_plane=device_full&chunk=8",
                           4 << 30, expected_entries=64)
        keys = np.arange(16, dtype=np.int64)
        sizes = np.full(16, 1000, np.int64)
        with pytest.raises(ValueError, match="int32"):
            if drive == "sequential":
                p.access_batch(keys, sizes)
            else:
                eng = FleetEngine()
                eng.add(p, keys, sizes)
                eng.run()

    def test_host_sync_guards_restore_authority(self):
        """Scalar ``access`` between chunked drives must transparently
        restore host authority (download + rebuild), ``__contains__`` must
        answer exactly from the device without taking authority back, and
        the run must stay byte-identical to a pure-scalar replay."""
        rng = np.random.default_rng(11)
        keys = ((rng.zipf(1.2, size=420) - 1) % 32).astype(np.int64).tolist()
        sizes = [10 + (k * 13) % 70 for k in keys]
        spec = "wtlfu-av-slru?sketch_backend=cms"
        a = REGISTRY.build(spec, 700, data_plane="scalar", expected_entries=64)
        ha = [a.access(k, s) for k, s in zip(keys, sizes)]
        r = REGISTRY.build(spec, 700, data_plane="scalar", expected_entries=64)
        e = REGISTRY.build(spec, 700, data_plane="device_full",
                           expected_entries=64, chunk=16)
        plane = e._device_pipeline
        he = []
        # interleave chunk drives with scalar accesses and membership reads
        i = 0
        while i < len(keys):
            take = 48 if (i // 48) % 2 == 0 else 5
            block_k, block_s = keys[i:i + take], sizes[i:i + take]
            for k, s in zip(block_k, block_s):
                r.access(k, s)  # the scalar reference, in lockstep
            if take == 5:  # scalar path: forces ensure_host via the guard
                he.extend(e.access(k, s) for k, s in zip(block_k, block_s))
                assert not plane.has_deferred_work
            else:
                he.extend(bool(h) for h in e.access_batch(
                    np.asarray(block_k, np.int64), np.asarray(block_s, np.int64)))
                # membership read mid-run: exact for every key, read off the
                # device, and the state stays there (no download + re-upload)
                uploads = plane.uploads
                assert [k in e for k in range(40)] == [k in r for k in range(40)]
                assert plane.has_deferred_work and not plane._host_auth
                assert plane.uploads == uploads
            i += take
        e.sync_deferred()
        _assert_byte_identical(a, e, np.asarray(ha), np.asarray(he),
                               "host-sync guards")

    @pytest.mark.parametrize("eviction", ("slru", "sampled_frequency"))
    def test_membership_waits_only_on_a_launch_that_misses(self, eviction):
        """``__contains__`` reads the resident keys off the device once and
        keeps them across deferred launches whose keys are all resident
        (all hits, so residency cannot change: no wait on the launch); a
        launch with a miss is collected before the next answer. Every
        answer equals a scalar replay driven in lockstep."""
        spec = f"wtlfu-av-{eviction}?sketch_backend=cms"
        r = REGISTRY.build(spec, 2_000, data_plane="scalar", expected_entries=64)
        e = REGISTRY.build(spec, 2_000, data_plane="device_full",
                           expected_entries=64, chunk=16)
        plane = e._device_pipeline
        plane.defer_collect = True
        probes = range(80)

        def drive(keys):
            sizes = [20 + (k * 7) % 60 for k in keys]
            for k, s in zip(keys, sizes):
                r.access(k, s)
            e.access_batch(np.asarray(keys, np.int64), np.asarray(sizes, np.int64))

        def exact():
            return [k in e for k in probes] == [k in r for k in probes]

        drive(list(range(16)))
        assert plane._inflight is not None
        assert exact() and plane._inflight is None  # first read collects
        resident = [k for k in probes if k in r]
        drive([resident[i % len(resident)] for i in range(16)])  # all hits
        assert plane._inflight is not None
        assert exact()
        assert plane._inflight is not None, "an all-hit launch was waited for"
        drive(list(range(60, 76)))  # misses: residency changes
        assert exact() and plane._inflight is None
        assert plane.uploads == 1 and not plane._host_auth
        e.sync_deferred()
        assert sorted(e.window) == sorted(r.window)
        assert sorted(e.main.sizes.items()) == sorted(r.main.sizes.items())

    def test_serving_defer_collect_double_buffers(self):
        """The serving async pipeline drives device_full unchanged through
        the shared plane surface: whole-chunk drains stay in flight on
        device (``deferred_dispatches``) and sync() settles them."""
        from repro.serving.admission import AsyncAdmissionPipeline

        p = REGISTRY.build(
            "wtlfu-qv-sampled_frequency?data_plane=device_full&chunk=32",
            5_000, expected_entries=64)
        pipe = AsyncAdmissionPipeline(p)
        assert p._device_pipeline.defer_collect is True
        assert pipe.queue_chunk == 32
        rng = np.random.default_rng(7)
        for i in range(256):
            k = int(rng.integers(0, 48))
            pipe.offer(k, 40 + k % 50)
        pipe.sync()
        plane = p._device_pipeline
        assert plane.deferred_dispatches > 0, "defer path never engaged"
        # settled: the launch is collected, and the verdicts were read off
        # the device without taking authority back (one upload in all)
        assert plane._inflight is None and plane.uploads == 1
        m = pipe.metrics()
        assert m["decisions"] == plane.decisions


class TestFusedSketchPath:
    def _drive(self, fused: bool):
        from repro.core.cms_sketch import CMSSketch

        sk = CMSSketch(128, flush_block=64 if fused else 1_000_000)
        rnd = random.Random(3)
        outs = []
        for _ in range(20):
            sk.increment_batch([rnd.randint(0, 500) for _ in range(rnd.randint(0, 50))])
            if not fused:
                sk.flush()  # staged: flush first, estimate on a clean table
            outs.append(sk.estimate_batch([rnd.randint(0, 500) for _ in range(5)]).tolist())
        return outs, np.asarray(sk.table).tolist(), sk.resets, sk._ops

    def test_fused_equals_staged_flush_then_estimate(self):
        """The fused update+estimate kernel call must be indistinguishable
        from flush() followed by a plain estimate."""
        assert self._drive(fused=True) == self._drive(fused=False)

    def test_fused_respects_reset_boundary(self):
        from repro.core.cms_sketch import CMSSketch

        def run(flush_block):
            sk = CMSSketch(16, sample_factor=10, flush_block=flush_block)
            outs = []
            for i in range(6):
                sk.increment_batch(list(range(i * 40, i * 40 + 40)))
                outs.append(sk.estimate_batch([1, 2, 3]).tolist())
            return outs, sk.resets

        # flush_block=8 forces the staged path; 512 allows fusing — results
        # must agree even when batches straddle the aging reset.
        assert run(8) == run(512)
