"""Property-based differential suite: scalar vs batched vs device_full.

With counter-based victim sampling every eviction policy is peek-stable,
so ``data_plane="batched"`` must be **byte-identical** to ``"scalar"`` —
same hit/miss decision stream, same ``CacheStats`` counters, same final
cache contents — for every admission x eviction combo, sampled evictions
included. ``data_plane="device_full"`` (CMS backend) must match both host
planes over the same 21-combo grid, driven two ways: through scalar
``access``, where each admission decision is one jitted per-decision
kernel call, and through ``access_batch``, where the WHOLE simulation
step — window hits, recency updates, miss cascade, adaptive climber —
runs in one ``lax.scan`` per chunk with the cache state device-resident
between chunks. The chunked drive must match on decisions, stats, final
contents, window occupancy, and the adaptive ``window_cap`` trajectory,
with host resyncs only on sketch aging resets and mirror growth (both
test-forced below). Every member of a vmapped ``FleetEngine`` sweep
(``TestFleetDifferential``) must match the sequential ``device_full`` loop
per instance — hit stream, stats, contents, resync/upload counters —
including test-forced per-lane aging and mirror-growth resyncs inside a
mixed multi-bucket fleet.

Four layers:

* a **seeded exhaustive grid** over all 21 combos that runs without
  hypothesis (tier-1), re-seedable via ``REPRO_DIFF_SEED`` (the nightly CI
  seed-matrix job reruns it under several fixed seeds);
* the **device-plane grid**: the same 21 combos under ``sketch_backend=
  "cms"``, asserting scalar == batched == device_full by ``access`` ==
  device_full by ``access_batch`` (decisions, CacheStats, final cache
  contents, sampling fallback counters), same reseeding;
* **hypothesis properties** generating random traces (key skew, size
  distributions, capacities) and random ``PolicySpec`` strings (window
  fraction, pruning, ``?seed=``), asserting plane equivalence and spec
  round-tripping — skipped cleanly when hypothesis is absent
  (``_hypothesis_compat``);
* a ``slow``-marked CMS-backend differential sweep (Pallas interpret mode
  is correct but not fast on CPU), for the nightly run.
"""

import os
import zlib

import numpy as np
import pytest
from _hypothesis_compat import HealthCheck, given, settings, st

from repro.core import REGISTRY, PolicySpec

#: Base seed for the exhaustive grid; the nightly seed-matrix job sets it.
DIFF_SEED = int(os.environ.get("REPRO_DIFF_SEED", "0"))

ADMISSIONS = ("iv", "qv", "av")
EVICTIONS = (
    "lru",
    "slru",
    "sampled_frequency",
    "sampled_size",
    "sampled_frequency_size",
    "sampled_needed_size",
    "random",
)
ALL_COMBOS = [(a, e) for a in ADMISSIONS for e in EVICTIONS]


def _combo_key(admission: str, eviction: str) -> int:
    # crc32, not hash(): str hashing is randomized per process, which would
    # silently vary the generated traces between runs.
    return zlib.crc32(f"{admission}/{eviction}".encode()) & 0x7FFFFFFF


def _synth_trace(rng: np.random.Generator, n: int, key_space: int, size_mode: str):
    """Key-skewed trace with per-key-stable sizes in the chosen regime."""
    keys = (rng.zipf(1.25, size=n) - 1) % key_space
    if size_mode == "uniform":
        per_key = rng.integers(8, 120, size=key_space)
    elif size_mode == "clustered":
        per_key = rng.choice([16, 64, 256], size=key_space, p=[0.5, 0.35, 0.15])
    else:  # heavytail
        per_key = np.minimum(8 + (rng.pareto(1.1, size=key_space) * 40).astype(np.int64), 4000)
    sizes = per_key[keys]
    return keys.astype(np.int64).tolist(), sizes.astype(np.int64).tolist()


def _run_plane(spec, capacity, keys, sizes, plane, **kw):
    p = REGISTRY.build(spec, capacity, data_plane=plane, **kw)
    hits = []
    for k, s in zip(keys, sizes):
        hits.append(p.access(k, s))
        assert p.used_bytes() <= p.capacity, "capacity invariant violated"
    return p, hits


def _run_plane_chunked(spec, capacity, keys, sizes, plane, step=29, **kw):
    """Drive via ``access_batch`` in uneven chunks — under ``device_full``
    this is the path that runs the chunk program (access-by-access it
    degenerates to the per-decision kernel)."""
    p = REGISTRY.build(spec, capacity, data_plane=plane, **kw)
    hits = []
    ka = np.asarray(keys, dtype=np.int64)
    sa = np.asarray(sizes, dtype=np.int64)
    for lo in range(0, len(ka), step):
        hits.extend(bool(h) for h in p.access_batch(ka[lo:lo + step], sa[lo:lo + step]))
        assert p.used_bytes() <= p.capacity, "capacity invariant violated"
    return p, hits


def _assert_identical(a, b, hits_a, hits_b, label):
    assert hits_a == hits_b, f"{label}: hit/miss streams diverge"
    sa, sb = a.stats, b.stats
    for field in ("accesses", "hits", "bytes_requested", "bytes_hit",
                  "victims_examined", "admissions", "rejections", "evictions"):
        assert getattr(sa, field) == getattr(sb, field), f"{label}: stats.{field}"
    assert list(a.window.items()) == list(b.window.items()), f"{label}: window"
    assert a.main.sizes == b.main.sizes, f"{label}: main contents"
    assert a.used_bytes() == b.used_bytes(), f"{label}: used bytes"


class TestSeededGrid:
    """Exhaustive combo grid, hypothesis-free (always runs in tier-1)."""

    @pytest.mark.parametrize("admission,eviction", ALL_COMBOS)
    def test_planes_byte_identical(self, admission, eviction):
        rng = np.random.default_rng([DIFF_SEED, _combo_key(admission, eviction)])
        for trial, size_mode in enumerate(("uniform", "clustered", "heavytail")):
            keys, sizes = _synth_trace(rng, n=500, key_space=40, size_mode=size_mode)
            cap = max(120, int(np.mean(sizes) * 8))
            spec = f"wtlfu-{admission}-{eviction}?window_frac=0.1&seed={DIFF_SEED + trial}"
            out = [
                _run_plane(spec, cap, keys, sizes, plane, expected_entries=64)
                for plane in ("scalar", "batched")
            ]
            (a, ha), (b, hb) = out
            _assert_identical(a, b, ha, hb, f"{spec} [{size_mode}]")
            assert a.stats.evictions > 0, f"{spec} [{size_mode}]: trace never evicted"

    def test_spec_seed_round_trip(self):
        """?seed= plumbs through PolicySpec (decimal and hex) and reaches
        the sampled eviction policy."""
        s = PolicySpec.parse("wtlfu-av-random?seed=0x5EED")
        assert s.params_dict["seed"] == 0x5EED
        assert PolicySpec.parse(s.to_string()) == s
        assert PolicySpec.parse("wtlfu-av-random?seed=24301") == s
        p = REGISTRY.build("wtlfu-qv-sampled_frequency?seed=0xA11CE", 1000,
                           expected_entries=32)
        assert p.main.seed == 0xA11CE

    def test_different_seeds_diverge(self):
        """The ?seed= knob is live: distinct seeds sample distinct victims
        (same trace, same policy, different eviction streams)."""
        rng = np.random.default_rng(DIFF_SEED + 99)
        keys, sizes = _synth_trace(rng, n=800, key_space=30, size_mode="uniform")
        cap = max(120, int(np.mean(sizes) * 6))
        outs = []
        for seed in (1, 2):
            p, hits = _run_plane(f"wtlfu-av-random?seed={seed}", cap, keys, sizes,
                                 "batched", expected_entries=64)
            outs.append((hits, sorted(p.main.sizes)))
        assert outs[0] != outs[1]


class TestDeviceSeededGrid:
    """``data_plane="device_full"`` driven through scalar ``access`` — the
    closed sample->score->select per-decision kernel — and through
    ``access_batch`` — the chunk program — is byte-identical to BOTH host
    planes for every admission x eviction combo under the CMS backend,
    reseedable via ``REPRO_DIFF_SEED``."""

    @pytest.mark.parametrize("admission,eviction", ALL_COMBOS)
    def test_five_planes_byte_identical(self, admission, eviction):
        rng = np.random.default_rng([DIFF_SEED, 0xDE1CE, _combo_key(admission, eviction)])
        keys, sizes = _synth_trace(rng, n=220, key_space=32, size_mode="uniform")
        cap = max(120, int(np.mean(sizes) * 8))
        spec = (f"wtlfu-{admission}-{eviction}"
                f"?window_frac=0.1&seed={DIFF_SEED}&sketch_backend=cms")
        out = [
            _run_plane(spec, cap, keys, sizes, plane, expected_entries=64)
            for plane in ("scalar", "batched", "device_full")
        ]
        out.append(_run_plane_chunked(spec, cap, keys, sizes, "device_full",
                                      expected_entries=64, chunk=4))
        (a, ha), (b, hb), (c, hc), (e, he) = out
        _assert_identical(a, b, ha, hb, f"{spec} scalar-vs-batched")
        _assert_identical(a, c, ha, hc, f"{spec} scalar-vs-device_full access")
        e.sync_deferred()  # restore host authority before content compares
        _assert_identical(a, e, ha, he, f"{spec} scalar-vs-device_full chunks")
        assert a.stats.evictions > 0, f"{spec}: trace never evicted"
        if eviction not in ("lru", "slru"):
            assert a.main.fallback_scans == c.main.fallback_scans, \
                f"{spec}: device_full access fallback-scan count diverges"
            assert a.main.fallback_scans == e.main.fallback_scans, \
                f"{spec}: device_full chunks fallback-scan count diverges"

    @pytest.mark.parametrize("eviction", ("sampled_frequency", "slru"))
    def test_device_pallas_branch_matches_scalar(self, eviction):
        """The decision kernel's Pallas branch (``use_pallas=True``, the
        TPU path — fused ``cms_update_estimate`` launch incl. the padded
        update-lane sentinel masking) must match the scalar reference too;
        off-TPU the default resolves to the value-identical jnp branch, so
        without forcing it this path would only ever run on TPU."""
        rng = np.random.default_rng([DIFF_SEED, 0x9A11A5, _combo_key("av", eviction)])
        keys, sizes = _synth_trace(rng, n=100, key_space=24, size_mode="uniform")
        cap = max(120, int(np.mean(sizes) * 8))
        spec = f"wtlfu-av-{eviction}?seed={DIFF_SEED}&sketch_backend=cms"
        # scalar reference on the default (jnp) branch: estimates are pure
        # table reads, so use_pallas cannot change its values — and Pallas
        # interpret mode per scalar estimate would dominate the suite
        a, ha = _run_plane(spec, cap, keys, sizes, "scalar", expected_entries=64)
        c, hc = _run_plane(spec, cap, keys, sizes, "device_full",
                           expected_entries=64, sketch_kwargs={"use_pallas": True})
        assert c.sketch.use_pallas
        _assert_identical(a, c, ha, hc, f"{spec} device_full/use_pallas=True")

    @pytest.mark.parametrize("admission,eviction",
                             [("iv", "random"), ("qv", "sampled_frequency"), ("av", "slru")])
    def test_three_planes_across_aging_resets(self, admission, eviction):
        """A small sketch forces aging resets mid-trace: the per-decision
        kernel must stage its pending flush at the same boundaries the host
        planes do (same resets, same tables, same decisions)."""
        rng = np.random.default_rng([DIFF_SEED, 0xA61, _combo_key(admission, eviction)])
        keys, sizes = _synth_trace(rng, n=400, key_space=40, size_mode="clustered")
        cap = max(120, int(np.mean(sizes) * 8))
        spec = f"wtlfu-{admission}-{eviction}?seed={DIFF_SEED}&sketch_backend=cms"
        out = [
            _run_plane(spec, cap, keys, sizes, plane, expected_entries=16)
            for plane in ("scalar", "device_full")
        ]
        (a, ha), (c, hc) = out
        assert a.sketch.resets > 0, "trace too short to age the sketch"
        assert a.sketch.resets == c.sketch.resets
        _assert_identical(a, c, ha, hc, f"{spec} across resets")


class TestDeviceFullResyncs:
    """ISSUE 7: device_full keeps the cache state device-resident; the only
    host resyncs are sketch aging resets and mirror growth. Both are forced
    here, counted, and shown not to break identity — and the adaptive
    climber + SLRU promotion run INSIDE the scan (the ``window_cap``
    trajectory and protected-segment contents must replay exactly)."""

    def _caps_run(self, spec, cap, keys, sizes, plane, *, chunk=None, **kw):
        """Chunked drive recording ``window_cap`` after every chunk (for
        device_full those scalars commit at collect — no host sync)."""
        build_kw = dict(kw)
        if chunk is not None:
            build_kw["chunk"] = chunk
        p = REGISTRY.build(spec, cap, data_plane=plane, **build_kw)
        hits, caps = [], []
        ka = np.asarray(keys, dtype=np.int64)
        sa = np.asarray(sizes, dtype=np.int64)
        for lo in range(0, len(ka), 64):
            hits.extend(bool(h) for h in p.access_batch(ka[lo:lo + 64],
                                                        sa[lo:lo + 64]))
            caps.append(p.window_cap)
        return p, hits, caps

    @pytest.mark.parametrize("admission,eviction",
                             [("av", "slru"), ("qv", "sampled_frequency"),
                              ("iv", "lru")])
    def test_adaptive_window_cap_trajectory(self, admission, eviction):
        """A high-miss trace fires the in-scan hill-climber repeatedly; the
        per-chunk ``window_cap`` trajectory (and everything downstream of
        the re-split: drains, decisions, contents) must match scalar."""
        rng = np.random.default_rng([DIFF_SEED, 0xADA, _combo_key(admission, eviction)])
        n = 2600
        keys = ((rng.zipf(1.05, size=n) - 1) % 2000).astype(np.int64).tolist()
        sizes = rng.integers(4, 40, size=n).astype(np.int64).tolist()
        spec = (f"wtlfu-{admission}-{eviction}?window_frac=0.05"
                f"&seed={DIFF_SEED}&sketch_backend=cms&adaptive_window=1")
        a, ha, caps_a = self._caps_run(spec, 3000, keys, sizes, "scalar",
                                       expected_entries=64)
        e, he, caps_e = self._caps_run(spec, 3000, keys, sizes, "device_full",
                                       chunk=64, expected_entries=64)
        assert len(set(caps_a)) >= 2, "trace never moved the window: weak test"
        assert caps_a == caps_e, f"{spec}: window_cap trajectory diverges"
        e.sync_deferred()
        _assert_identical(a, e, ha, he, f"{spec} adaptive")
        assert (a.window_cap, a.main_cap) == (e.window_cap, e.main_cap)
        assert a._adapt_accesses == e._adapt_accesses
        assert a._adapt_dir == e._adapt_dir
        assert a._adapt_prev_hits == e._adapt_prev_hits
        assert a._adapt_prev_ratio == e._adapt_prev_ratio

    def test_slru_promotion_and_segments(self):
        """SLRU main-hit promotion (probation -> protected, with
        protected-overflow demotion) happens in-scan; the per-entry segment
        assignment and protected byte count must replay exactly."""
        rng = np.random.default_rng([DIFF_SEED, 0x51F0])
        # narrow keyspace => plenty of main hits => promotions + demotions
        keys, sizes = _synth_trace(rng, n=900, key_space=24, size_mode="uniform")
        spec = f"wtlfu-av-slru?window_frac=0.1&seed={DIFF_SEED}&sketch_backend=cms"
        cap = max(300, int(np.mean(sizes) * 10))
        a, ha = _run_plane(spec, cap, keys, sizes, "scalar", expected_entries=64)
        e, he = _run_plane_chunked(spec, cap, keys, sizes, "device_full",
                                   expected_entries=64, chunk=16)
        e.sync_deferred()
        _assert_identical(a, e, ha, he, f"{spec} slru")
        assert len(a.main.protected) > 0, "no promotions happened: weak test"
        assert list(a.main.probation) == list(e.main.probation)
        assert list(a.main.protected) == list(e.main.protected)
        assert a.main.protected_bytes == e.main.protected_bytes

    @pytest.mark.parametrize("admission,eviction",
                             [("iv", "random"), ("qv", "sampled_needed_size"),
                              ("av", "slru")])
    def test_forced_aging_resync(self, admission, eviction):
        """A tiny sketch forces aging resets mid-chunk: the boundary access
        replays through the host path (counted as an ``aging`` resync) and
        the sketch ages at the exact same stream positions as scalar."""
        rng = np.random.default_rng([DIFF_SEED, 0xA6E, _combo_key(admission, eviction)])
        keys, sizes = _synth_trace(rng, n=400, key_space=40, size_mode="clustered")
        cap = max(120, int(np.mean(sizes) * 8))
        spec = f"wtlfu-{admission}-{eviction}?seed={DIFF_SEED}&sketch_backend=cms"
        a, ha = _run_plane(spec, cap, keys, sizes, "scalar", expected_entries=16)
        e, he = _run_plane_chunked(spec, cap, keys, sizes, "device_full",
                                   expected_entries=16, chunk=8)
        e.sync_deferred()
        assert a.sketch.resets > 0, "trace too short to age the sketch"
        assert a.sketch.resets == e.sketch.resets
        pipe = e._device_pipeline
        assert pipe.resync_reasons["aging"] > 0, "aging resync never forced"
        assert pipe.resyncs == sum(pipe.resync_reasons.values())
        _assert_identical(a, e, ha, he, f"{spec} across resets")

    @pytest.mark.parametrize("admission,eviction",
                             [("av", "slru"), ("qv", "sampled_frequency")])
    def test_pallas_sketch_across_padded_launches(self, admission, eviction):
        """The Pallas branch (``use_pallas=True``: the in-place one-key CMS
        kernel once per scan step) over batches of 29 on a chunk of 8, so
        every batch's last launch runs padded steps (``valid = 0``), with a
        tiny sketch forcing aging resets: the sketch table and its resets
        equal the scalar reference's."""
        rng = np.random.default_rng([DIFF_SEED, 0x9AD, _combo_key(admission, eviction)])
        keys, sizes = _synth_trace(rng, n=210, key_space=40, size_mode="clustered")
        keys = [k + 1 for k in keys]  # padded steps carry key 0: keep it out
        cap = max(120, int(np.mean(sizes) * 8))
        spec = f"wtlfu-{admission}-{eviction}?seed={DIFF_SEED}&sketch_backend=cms"
        a, ha = _run_plane(spec, cap, keys, sizes, "scalar", expected_entries=16)
        e, he = _run_plane_chunked(spec, cap, keys, sizes, "device_full",
                                   expected_entries=16, chunk=8,
                                   sketch_kwargs={"use_pallas": True})
        e.sync_deferred()
        assert e.sketch.use_pallas
        assert a.sketch.resets > 0, "trace too short to age the sketch"
        assert a.sketch.resets == e.sketch.resets
        a.sketch.flush()
        e.sketch.flush()
        np.testing.assert_array_equal(np.asarray(a.sketch.table),
                                      np.asarray(e.sketch.table))
        _assert_identical(a, e, ha, he, f"{spec} use_pallas=True")

    def test_forced_mirror_grow_resync(self):
        """A trace whose live-entry count keeps growing outruns the initial
        device slot arrays: the mirror zero-pads ON DEVICE (counted as a
        ``mirror_grow`` resync, no full re-upload) and identity holds."""
        rng = np.random.default_rng([DIFF_SEED, 0x960])
        n = 1600
        keys = np.arange(n, dtype=np.int64)  # all-miss: contents only grow
        keys[1::4] = keys[0::4][: len(keys[1::4])]  # some repeats for hits
        sizes = rng.integers(1, 6, size=n).astype(np.int64).tolist()
        spec = f"wtlfu-av-sampled_frequency?seed={DIFF_SEED}&sketch_backend=cms"
        a, ha = _run_plane(spec, 10**6, keys.tolist(), sizes, "scalar",
                           expected_entries=4096)
        e, he = _run_plane_chunked(spec, 10**6, keys.tolist(), sizes,
                                   "device_full", expected_entries=4096,
                                   chunk=64)
        e.sync_deferred()
        pipe = e._device_pipeline
        assert pipe.resync_reasons["mirror_grow"] > 0, "growth never forced"
        assert pipe.resync_reasons["aging"] == 0
        # growth is device-side padding, not a host re-upload
        assert pipe.uploads == 1
        _assert_identical(a, e, ha, he, f"{spec} across growth")


class TestFleetDifferential:
    """ISSUE 8 sixth column: the vmapped fleet drive. Every member of a
    multi-instance :class:`repro.kernels.fleet.FleetEngine` — mixed
    admission x eviction combos and per-instance seeds, shape-bucketed
    into separate vmapped launches — must be byte-identical to the SAME
    spec driven through the sequential ``device_full`` loop: hit stream,
    ``CacheStats``, final contents, and the resync/upload counters (the
    per-instance aging and mirror_grow paths are both test-forced)."""

    #: one combo per eviction kind, admissions rotating — the shape-bucket
    #: axes (rule, main kind, discipline) all vary across the fleet
    COMBOS = [("iv", "random"), ("qv", "sampled_frequency"),
              ("av", "slru"), ("av", "lru"),
              ("qv", "sampled_needed_size"), ("iv", "sampled_frequency_size"),
              ("av", "sampled_size")]
    SEEDS = (DIFF_SEED, DIFF_SEED + 1)

    def _sequential(self, spec, cap, keys, sizes, **kw):
        # one access_batch over the whole trace — the same drive pattern
        # the fleet uses, so chunk_calls line up exactly
        p, hits = _run_plane_chunked(spec, cap, list(keys), list(sizes),
                                     "device_full", step=len(keys), **kw)
        p.sync_deferred()
        return p, hits

    def test_fleet_grid_byte_identical_to_sequential(self):
        """The whole mixed grid rides ONE engine (7 combos x 2 seeds = 14
        lanes over 7 shape-buckets), with a small sketch sample forcing
        aging resyncs per instance mid-run."""
        from repro.kernels.fleet import FleetEngine

        rng = np.random.default_rng([DIFF_SEED, 0xF1EE7])
        keys, sizes = _synth_trace(rng, n=300, key_space=40,
                                   size_mode="clustered")
        cap = max(120, int(np.mean(sizes) * 8))
        specs = [
            (f"wtlfu-{a}-{e}?window_frac=0.1&seed={seed}"
             "&sketch_backend=cms")
            for a, e in self.COMBOS for seed in self.SEEDS
        ]
        eng = FleetEngine()
        members = [
            eng.add(REGISTRY.build(s, cap, data_plane="device_full",
                                   expected_entries=16, chunk=8),
                    keys, sizes, label=s)
            for s in specs
        ]
        eng.run()
        assert len(eng.buckets) == 0  # released
        aged = 0
        for s, m in zip(specs, members):
            a, ha = self._sequential(s, cap, keys, sizes,
                                     expected_entries=16, chunk=8)
            he = [bool(h) for h in m.hit_mask]
            _assert_identical(a, m.policy, ha, he, f"fleet:{s}")
            pa, pe = a._device_pipeline, m.policy._device_pipeline
            assert dict(pa.resync_reasons) == dict(pe.resync_reasons), s
            assert (pa.resyncs, pa.uploads, pa.chunk_calls) == \
                (pe.resyncs, pe.uploads, pe.chunk_calls), s
            aged += pe.resync_reasons["aging"]
        assert aged > 0, "aging resync never forced on any instance"
        # amortization invariant: the whole grid cost far fewer vmapped
        # launches than the members' summed chunk count
        total_chunks = sum(m.policy._device_pipeline.chunk_calls
                           for m in members)
        assert eng.launches < total_chunks, \
            f"no amortization: {eng.launches} launches vs {total_chunks}"

    def test_fleet_forced_mirror_grow_per_instance(self):
        """A growing-live-set member forces ``mirror_grow`` on ITS lane
        while a steady member shares the engine: both stay identical to
        their sequential twins and the growth counters match per
        instance."""
        from repro.kernels.fleet import FleetEngine

        rng = np.random.default_rng([DIFF_SEED, 0xF960])
        n = 1200
        gkeys = np.arange(n, dtype=np.int64)  # mostly-miss: contents grow
        gkeys[1::4] = gkeys[0::4][: len(gkeys[1::4])]
        gsizes = rng.integers(1, 6, size=n).astype(np.int64)
        zkeys, zsizes = _synth_trace(rng, n=n, key_space=30,
                                     size_mode="uniform")
        grow_spec = (f"wtlfu-av-sampled_frequency?seed={DIFF_SEED}"
                     "&sketch_backend=cms")
        steady_spec = (f"wtlfu-qv-sampled_frequency?seed={DIFF_SEED}"
                       "&sketch_backend=cms")
        zcap = max(120, int(np.mean(zsizes) * 8))
        eng = FleetEngine()
        gm = eng.add(REGISTRY.build(grow_spec, 10**6,
                                    data_plane="device_full",
                                    expected_entries=4096, chunk=64),
                     gkeys, gsizes)
        zm = eng.add(REGISTRY.build(steady_spec, zcap,
                                    data_plane="device_full",
                                    expected_entries=4096, chunk=64),
                     np.asarray(zkeys), np.asarray(zsizes))
        eng.run()
        ga, gha = self._sequential(grow_spec, 10**6, gkeys, gsizes,
                                   expected_entries=4096, chunk=64)
        za, zha = self._sequential(steady_spec, zcap, zkeys, zsizes,
                                   expected_entries=4096, chunk=64)
        for seq, seq_hits, m, label in ((ga, gha, gm, "grow"),
                                        (za, zha, zm, "steady")):
            he = [bool(h) for h in m.hit_mask]
            _assert_identical(seq, m.policy, seq_hits, he, f"fleet:{label}")
            pa, pe = seq._device_pipeline, m.policy._device_pipeline
            assert dict(pa.resync_reasons) == dict(pe.resync_reasons), label
            assert pa.uploads == pe.uploads, label
        ggrow = gm.policy._device_pipeline.resync_reasons["mirror_grow"]
        zgrow = zm.policy._device_pipeline.resync_reasons["mirror_grow"]
        assert ggrow > 0, "growth never forced"
        # growth is per-instance: the steady lane does not inherit the
        # growing lane's resyncs
        assert zgrow < ggrow


class TestHypothesisDifferential:
    @settings(max_examples=30, deadline=None,
              suppress_health_check=(HealthCheck.too_slow,))
    @given(
        admission=st.sampled_from(ADMISSIONS),
        eviction=st.sampled_from(EVICTIONS),
        key_space=st.integers(6, 120),
        n=st.integers(60, 400),
        size_mode=st.sampled_from(("uniform", "clustered", "heavytail")),
        cap_scale=st.floats(2.0, 20.0),
        window_frac=st.floats(0.02, 0.4),
        early_pruning=st.booleans(),
        seed=st.integers(0, 2**32 - 1),
        trace_seed=st.integers(0, 2**31 - 1),
    )
    def test_random_trace_random_spec(self, admission, eviction, key_space,
                                      n, size_mode, cap_scale, window_frac,
                                      early_pruning, seed, trace_seed):
        """Random trace x random spec string: planes byte-identical, spec
        round-trips."""
        rng = np.random.default_rng(trace_seed)
        keys, sizes = _synth_trace(rng, n=n, key_space=key_space, size_mode=size_mode)
        cap = max(100, int(np.mean(sizes) * cap_scale))
        params = f"window_frac={round(window_frac, 3)}&seed={seed}"
        if admission == "av":
            params += f"&early_pruning={int(early_pruning)}"
        spec_text = f"wtlfu-{admission}-{eviction}?{params}"
        spec = PolicySpec.parse(spec_text)
        assert PolicySpec.parse(spec.to_string()) == spec
        out = [
            _run_plane(spec, cap, keys, sizes, plane, expected_entries=64)
            for plane in ("scalar", "batched")
        ]
        (a, ha), (b, hb) = out
        _assert_identical(a, b, ha, hb, spec_text)

    @settings(max_examples=15, deadline=None,
              suppress_health_check=(HealthCheck.too_slow,))
    @given(
        entries=st.lists(
            st.tuples(st.integers(0, 5000), st.integers(1, 300)),
            min_size=1, max_size=50, unique_by=lambda kv: kv[0],
        ),
        eviction=st.sampled_from(EVICTIONS[2:]),
        needed_frac=st.floats(0.0, 1.3),
        decisions=st.integers(0, 5),
    )
    def test_sampled_peek_replays(self, entries, eviction, needed_frac, decisions):
        """Sampled policies: peek_victims is a pure replay at any decision
        index — peeking twice, or peeking then walking, must agree."""
        from repro.core.eviction import make_eviction

        e = make_eviction(eviction, capacity=10**9, freq_fn=lambda k: (k * 13) % 7)
        for k, s in entries:
            e.insert(k, s)
        for _ in range(decisions):
            e.begin_decision()
        needed = int(sum(s for _, s in entries) * needed_frac)
        k1, s1 = e.peek_victims(needed)
        k2, s2 = e.peek_victims(needed)
        assert k1.tolist() == k2.tolist() and s1.tolist() == s2.tolist()
        walked, total = [], 0
        if needed > 0:
            for v in e.iter_victims(needed):
                walked.append(v)
                total += e.sizes[v]
                if total >= needed:
                    break
        assert k1.tolist() == walked


@pytest.mark.slow
class TestCMSBackendDifferential:
    """Planes also agree under the CMS Pallas sketch backend (nightly —
    interpret mode makes this slow on CPU)."""

    @pytest.mark.parametrize("admission", ADMISSIONS)
    @pytest.mark.parametrize("eviction", ("sampled_frequency", "sampled_needed_size", "random"))
    def test_cms_planes_byte_identical(self, admission, eviction):
        rng = np.random.default_rng([DIFF_SEED, 0xC35, _combo_key(admission, eviction)])
        keys, sizes = _synth_trace(rng, n=250, key_space=30, size_mode="uniform")
        cap = max(120, int(np.mean(sizes) * 8))
        spec = f"wtlfu-{admission}-{eviction}?seed={DIFF_SEED}"
        out = [
            _run_plane(spec, cap, keys, sizes, plane,
                       expected_entries=64, sketch_backend="cms")
            for plane in ("scalar", "batched", "device_full")
        ]
        out.append(_run_plane_chunked(spec, cap, keys, sizes, "device_full",
                                      expected_entries=64, sketch_backend="cms",
                                      chunk=6))
        (a, ha), (b, hb), (c, hc), (d, hd) = out
        d.sync_deferred()
        _assert_identical(a, b, ha, hb, f"cms:{spec}")
        _assert_identical(a, c, ha, hc, f"cms-device_full-access:{spec}")
        _assert_identical(a, d, ha, hd, f"cms-device_full-chunks:{spec}")


class TestServingDifferential:
    """Decisions driven *through the serving layer* — ``PrefixCache``
    with the async admission pipeline (event queue -> ``access_batch`` ->
    deferred device chunk launches) must replay byte-identical
    to the synchronous per-access hook: same resident entries, same hit
    ratios, same policy stats, same window/main contents."""

    BLOCK = 4
    BPT = 10

    def _serve(self, spec: str, admission: str, combo_seed: int):
        from repro.serving import PrefixCache, PrefixCacheConfig

        cache = PrefixCache(PrefixCacheConfig(
            capacity_bytes=16 * self.BLOCK * self.BPT, block_size=self.BLOCK,
            bytes_per_token=self.BPT, policy=spec, admission=admission))
        rng = np.random.default_rng([DIFF_SEED, combo_seed])
        for i in range(400):
            tmpl = int((rng.zipf(1.3) - 1) % 14)
            length = (1 + tmpl % 4) * self.BLOCK
            prompt = [tmpl * 1000 + j for j in range(length)]
            cache.lookup(prompt + [10**6 + i])
            cache.offer(prompt)
        cache.sync()
        return cache

    def _assert_serving_identical(self, spec: str, combo_seed: int):
        sync = self._serve(spec, "sync", combo_seed)
        a = self._serve(spec, "async", combo_seed)
        for k in ("request_hit_ratio", "token_hit_ratio", "byte_hit_ratio"):
            assert getattr(sync, k) == getattr(a, k), f"{spec}: {k}"
        assert set(sync.entries) == set(a.entries), f"{spec}: entries"
        for f in ("accesses", "hits", "bytes_hit", "victims_examined",
                  "admissions", "rejections", "evictions"):
            assert getattr(sync.policy.stats, f) == getattr(a.policy.stats, f), (
                f"{spec}: stats.{f}")
        assert list(sync.policy.window.items()) == list(a.policy.window.items())
        assert sync.policy.main.sizes == a.policy.main.sizes
        assert sync.request_hit_ratio > 0, f"{spec}: degenerate regime"

    @pytest.mark.parametrize("admission,eviction", ALL_COMBOS)
    def test_host_plane_serving_identity(self, admission, eviction):
        spec = f"wtlfu-{admission}-{eviction}?window_frac=0.1&seed={DIFF_SEED}"
        self._assert_serving_identical(spec, _combo_key(admission, eviction))

    @pytest.mark.parametrize("admission", ADMISSIONS)
    def test_device_full_serving_identity(self, admission):
        spec = (f"wtlfu-{admission}-sampled_frequency?seed={DIFF_SEED}"
                "&data_plane=device_full&chunk=16&sketch_backend=cms")
        self._assert_serving_identical(spec, 0x5E41 + _combo_key(admission, "d"))
