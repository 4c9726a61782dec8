"""How one ``device_full`` launch crosses to and from the device.

``chunk_args`` hands ``_simulate_chunk`` the five host-built launch inputs
(key limbs, sizes, packed scalars, RNG limbs) as NumPy arrays, so the jitted
call moves them in its own dispatch, and the ten state buffers as the arrays
adopted from the previous launch. Every launch passes the same kinds of
argument, the first after an aging re-upload or a mirror grow included, so
each shape holds one compiled entry. The results stay the scalar plane's.
"""

import numpy as np
import pytest

from repro.core import REGISTRY
from repro.kernels import device_full as df

SPECS = {"slru": "wtlfu-av-slru?seed=3&sketch_backend=cms",
         "sampled": "wtlfu-qv-sampled_frequency?seed=3&sketch_backend=cms"}
JNP_SKETCH = {"use_pallas": False}
STAT_FIELDS = ("accesses", "hits", "bytes_hit", "bytes_requested",
               "victims_examined", "admissions", "rejections", "evictions")


def _trace(n=480, key_space=200, seed=11):
    rng = np.random.default_rng(seed)
    keys = ((rng.zipf(1.2, size=n) - 1) % key_space).astype(np.int64)
    per_key = rng.integers(4, 12, size=key_space)
    return keys, per_key[keys].astype(np.int64)


def _build(main, plane="device_full"):
    """A cache with a tiny sketch (ages every 160 accesses) and room for
    more entries than its first mirror holds."""
    kw = {"chunk": 16} if plane == "device_full" else {}
    return REGISTRY.build(SPECS[main], 1200, data_plane=plane, expected_entries=16,
                          sketch_kwargs=JNP_SKETCH, **kw)


def _drive(p, keys, sizes, step=40, defer=False):
    p._device_pipeline.defer_collect = defer
    hits = []
    for lo in range(0, len(keys), step):
        hits.extend(bool(h) for h in np.asarray(
            p.access_batch(keys[lo:lo + step], sizes[lo:lo + step])))
    p.sync_deferred()
    return hits


class Recorder:
    """Wraps ``_simulate_chunk``: each launch's arguments and outputs, and
    whether the mirror was uploaded or grown since the launch before."""

    def __init__(self, real, pipe):
        self.real, self.pipe = real, pipe
        self.launches = []  # (args, outs, state_replaced)
        self._marks = None

    def __call__(self, *args, **kw):
        marks = (self.pipe.mirror.uploads, self.pipe.mirror.grows)
        outs = self.real(*args, **kw)
        self.launches.append((args, outs, marks != self._marks))
        self._marks = marks
        return outs


@pytest.mark.parametrize("main", sorted(SPECS))
def test_launches_take_numpy_inputs_and_the_adopted_state(main, monkeypatch):
    keys, sizes = _trace()
    p = _build(main)
    pipe = p._device_pipeline
    rec = Recorder(df._simulate_chunk, pipe)
    monkeypatch.setattr(df, "_simulate_chunk", rec)
    _drive(p, keys, sizes)
    assert pipe.resync_reasons["aging"] > 0, "the trace never ages the sketch"
    assert pipe.resync_reasons["mirror_grow"] > 0, "the mirror never grows"

    steady = 0
    for k, (args, outs, replaced) in enumerate(rec.launches):
        assert len(args) == 15 and len(outs) == 13
        assert all(type(a) is np.ndarray for a in args[10:]), k
        assert [a.dtype for a in args[10:]] == [np.int32] * 4 + [np.uint32], k
        if not replaced:
            prev = rec.launches[k - 1][1]
            assert all(a is b for a, b in zip(args[:10], prev[:10])), k
            steady += 1
    assert steady >= len(rec.launches) // 2, "too few steady launches to judge"


@pytest.mark.parametrize("main", sorted(SPECS))
def test_one_compiled_entry_a_shape(main, monkeypatch):
    """A first run compiles one entry for each shape it launches; a second
    cache over the same trace compiles nothing after its first launch,
    across its aging resyncs and its mirror grow."""
    keys, sizes = _trace()
    real = df._simulate_chunk
    real.clear_cache()
    first = _build(main)
    rec = Recorder(real, first._device_pipeline)
    monkeypatch.setattr(df, "_simulate_chunk", rec)
    _drive(first, keys, sizes)
    shapes = {tuple((a.shape, a.dtype) for a in args) for args, _, _ in rec.launches}
    assert real._cache_size() == len(shapes) > 1

    second = _build(main)
    pipe = second._device_pipeline
    entries = []

    def counting(*args, **kw):
        outs = real(*args, **kw)
        entries.append(real._cache_size())
        return outs

    monkeypatch.setattr(df, "_simulate_chunk", counting)
    _drive(second, keys, sizes)
    assert pipe.resync_reasons["aging"] > 0 and pipe.resync_reasons["mirror_grow"] > 0
    assert len(entries) > 20
    assert entries[1:] == [entries[0]] * (len(entries) - 1)


@pytest.mark.parametrize("defer", [False, True], ids=["collected", "deferred"])
@pytest.mark.parametrize("main", sorted(SPECS))
def test_results_equal_the_scalar_plane(main, defer):
    """Over a trace that crosses aging boundaries, with the launch left in
    flight (as the serving pipeline drives it) or collected at once."""
    keys, sizes = _trace()
    p = _build(main)
    # a deferred launch is one whole batch: batches of one chunk
    hits = _drive(p, keys, sizes, step=16 if defer else 40, defer=defer)
    pipe = p._device_pipeline
    assert pipe.resync_reasons["aging"] > 0, "the trace never ages the sketch"
    assert (pipe.deferred_dispatches > 0) == defer

    scalar = _build(main, plane="scalar")
    assert hits == [scalar.access(int(k), int(s)) for k, s in zip(keys, sizes)]
    for pol in (p, scalar):
        pol.sketch.flush()
    for f in STAT_FIELDS:
        assert getattr(p.stats, f) == getattr(scalar.stats, f), f
    assert list(p.window.items()) == list(scalar.window.items())
    assert p.main.sizes == scalar.main.sizes
    assert np.array_equal(np.asarray(p.sketch.table), np.asarray(scalar.sketch.table))
    assert p.sketch.resets == scalar.sketch.resets > 0



@pytest.mark.parametrize("main", sorted(SPECS))
def test_deferred_takes_run_their_own_bucket(main, monkeypatch):
    """A deferred drive (the serving pipeline's) scans its take's
    power-of-two bucket, at least 8 and at most the plane's scan length; a
    collected drive keeps that one length. Both equal the scalar plane."""
    keys, sizes = _trace(n=300)
    cuts = np.cumsum([0] + [3, 11, 16] * 10)
    pads = {}
    for defer in (False, True):
        p = _build(main)
        pipe = p._device_pipeline
        pipe.defer_collect = defer
        rec = Recorder(df._simulate_chunk, pipe)
        monkeypatch.setattr(df, "_simulate_chunk", rec)
        hits = []
        for lo, hi in zip(cuts[:-1], cuts[1:]):
            # a deferred launch hands back its padded mask
            hits.extend(bool(h) for h in np.asarray(
                p.access_batch(keys[lo:hi], sizes[lo:hi]))[:hi - lo])
        p.sync_deferred()
        assert pipe.scan_len() == 16
        pads[defer] = [(args[10].shape[0], int(args[13][df._SCAL_IDX["a_n"]]))
                       for args, _, _ in rec.launches]
        scalar = _build(main, plane="scalar")
        assert hits == [scalar.access(int(k), int(s))
                        for k, s in zip(keys[:cuts[-1]], sizes[:cuts[-1]])]
        assert p.main.sizes == scalar.main.sizes
        assert list(p.window.items()) == list(scalar.window.items())
    assert {pad for pad, _ in pads[False]} == {16}
    assert all(pad == (8 if take <= 8 else 16) for pad, take in pads[True])
    assert {pad for pad, _ in pads[True]} == {8, 16}
