"""The ``device_full`` plane's host spans (``device_full.PLANE_SPANS``).

Each launch opens ``plane.prepare`` (once per ``drive_chunk``, up to its
first launch), then ``plane.pack``, ``plane.enqueue``, ``plane.wait``,
``plane.fetch`` and ``plane.commit``, all tagged with the launch's index;
each counted resync opens one ``plane.resync`` with its reason. The spans
record only: the hit stream stays the scalar plane's.
"""

import contextlib
import glob

import numpy as np

from repro.core import REGISTRY
from repro.kernels import device_full as df

PREPARE, PACK, ENQUEUE, WAIT, FETCH, COMMIT, RESYNC = df.PLANE_SPANS
LAUNCH = (PACK, ENQUEUE, WAIT, FETCH, COMMIT)
SPEC = "wtlfu-av-slru?seed=3&sketch_backend=cms"
JNP_SKETCH = {"use_pallas": False}


class SpanRecorder:
    """Stands in for ``TraceAnnotation``: records each span's opening and
    closing, with its keyword arguments."""

    def __init__(self):
        self.events = []  # ("open" | "close", name, kwargs)

    @contextlib.contextmanager
    def __call__(self, name, **kw):
        self.events.append(("open", name, kw))
        try:
            yield
        finally:
            self.events.append(("close", name, kw))


def _trace(n=480, key_space=200, seed=11):
    rng = np.random.default_rng(seed)
    keys = ((rng.zipf(1.2, size=n) - 1) % key_space).astype(np.int64)
    per_key = rng.integers(4, 12, size=key_space)
    return keys, per_key[keys].astype(np.int64)


def _drive(keys, sizes, step, **kw):
    """A ``device_full`` cache over ``keys`` in ``access_batch`` calls of
    ``step``, with a tiny sketch (ages every 160 accesses) and room for
    more entries than its first mirror holds."""
    p = REGISTRY.build(SPEC, 1200, data_plane="device_full", expected_entries=16,
                       chunk=16, sketch_kwargs=JNP_SKETCH, **kw)
    hits = []
    for lo in range(0, len(keys), step):
        hits.extend(bool(h) for h in p.access_batch(keys[lo:lo + step],
                                                    sizes[lo:lo + step]))
    p.sync_deferred()
    return p, hits


def test_spans_follow_each_launch(monkeypatch):
    keys, sizes = _trace()
    rec = SpanRecorder()
    monkeypatch.setattr(df, "_span", rec)
    step = 40
    p, hits = _drive(keys, sizes, step)
    pipe = p._device_pipeline
    assert pipe.resync_reasons["aging"] > 0, "the trace never ages the sketch"
    assert pipe.resync_reasons["mirror_grow"] > 0, "the mirror never grows"

    # well nested, every span closed, and prepare closed before any launch
    stack = []
    for ev, name, kw in rec.events:
        if ev == "open":
            assert not (name == PACK and PREPARE in stack), "prepare open at a launch"
            stack.append((name, kw))
        else:
            assert stack.pop() == (name, kw)
    assert stack == []

    opens = [(name, kw) for ev, name, kw in rec.events if ev == "open"]
    resyncs = [(i, kw) for i, (name, kw) in enumerate(opens) if name == RESYNC]
    phases = [(name, kw) for name, kw in opens if name != RESYNC]
    launch = prepares = 0
    i = 0
    while i < len(phases):
        if phases[i][0] == PREPARE:
            assert phases[i][1] == {"launch": launch}
            prepares += 1
            i += 1
            continue
        assert phases[i:i + len(LAUNCH)] == [(n, {"launch": launch}) for n in LAUNCH]
        launch += 1
        i += len(LAUNCH)
    assert launch == pipe.chunk_calls
    assert prepares == len(range(0, len(keys), step))  # one per drive_chunk

    # one resync span per counted resync, with its reason: a mirror grow
    # inside the pack of its launch, an aging resync between launches,
    # tagged with the next one
    reasons = [kw["reason"] for _, kw in resyncs]
    assert {r: reasons.count(r) for r in pipe.resync_reasons} == pipe.resync_reasons
    for i, kw in resyncs:
        packs = [o for o in opens[:i] if o[0] == PACK]
        if kw["reason"] == "mirror_grow":
            assert packs[-1] == (PACK, {"launch": kw["launch"]})
            assert opens[i + 1] == (ENQUEUE, {"launch": kw["launch"]})
        else:
            assert kw["launch"] == len(packs)

    scalar = REGISTRY.build(SPEC, 1200, data_plane="scalar", expected_entries=16,
                            sketch_kwargs=JNP_SKETCH)
    assert hits == [scalar.access(int(k), int(s)) for k, s in zip(keys, sizes)]
    for pol in (p, scalar):
        pol.sketch.flush()
    assert p.stats.hits == scalar.stats.hits
    assert p.sketch.resets == scalar.sketch.resets > 0


def test_spans_on_a_profiler_host_plane(tmp_path):
    """A real ``jax.profiler`` trace holds the spans by name, with their
    launch index, on a host plane."""
    import jax
    from jax.profiler import ProfileData

    keys, sizes = _trace(n=96)
    with jax.profiler.trace(str(tmp_path)):
        p, _ = _drive(keys, sizes, 48)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in df.PLANE_SPANS:
                        launch = dict(ev.stats).get("launch")
                        found.setdefault(ev.name, set()).add(launch)
    launches = set(range(p._device_pipeline.chunk_calls))
    for name in LAUNCH:
        assert found.get(name) == launches, name
    assert found[PREPARE] == {0, 3}  # the two drive_chunk calls' first launches



def test_deferred_launch_collects_under_its_own_index(monkeypatch):
    """A launch left in flight (``defer_collect``, as the serving pipeline
    drives the plane) is collected inside the next ``plane.prepare``, still
    tagged with its own index."""
    rec = SpanRecorder()
    monkeypatch.setattr(df, "_span", rec)
    keys, sizes = _trace(n=128)
    p = REGISTRY.build(SPEC, 1200, data_plane="device_full", expected_entries=256,
                       chunk=64, sketch_kwargs=JNP_SKETCH)
    p._device_pipeline.defer_collect = True
    for lo in (0, 64):
        p.access_batch(keys[lo:lo + 64], sizes[lo:lo + 64])
    p.sync_deferred()
    assert p._device_pipeline.deferred_dispatches == 2
    opens = [(name, kw["launch"]) for ev, name, kw in rec.events
             if ev == "open" and name != RESYNC]
    assert opens == [(PREPARE, 0), (PACK, 0), (ENQUEUE, 0),
                     (PREPARE, 1), (WAIT, 0), (FETCH, 0), (COMMIT, 0),
                     (PACK, 1), (ENQUEUE, 1), (WAIT, 1), (FETCH, 1), (COMMIT, 1)]
    assert p.stats.accesses == 128
