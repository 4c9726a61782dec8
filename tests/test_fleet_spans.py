"""The fleet round's host spans (``fleet.FLEET_SPANS``) and its lane counters.

Each round opens ``fleet.take`` (aging replays inside, one ``fleet.resync``
per counted resync), ``fleet.upload``, ``fleet.pack``, ``fleet.enqueue``,
``fleet.wait``, ``fleet.fetch`` and ``fleet.commit``, all tagged with the
round's launch index. ``lane_uploads`` and ``lane_restores`` count the
lanes scattered into and gathered out of the stacks. The spans record
only: every member stays its sequential ``device_full`` run.
"""

import contextlib
import glob

import numpy as np
import pytest

from repro.core import REGISTRY
from repro.kernels import device_full as df
from repro.kernels import fleet

TAKE, RESYNC, UPLOAD, PACK, ENQUEUE, WAIT, FETCH, COMMIT = fleet.FLEET_SPANS
ROUND = (TAKE, UPLOAD, PACK, ENQUEUE, WAIT, FETCH, COMMIT)
SPEC = "wtlfu-av-slru?seed=3&sketch_backend=cms"
KW = dict(data_plane="device_full", expected_entries=16, chunk=16,
          sketch_kwargs={"use_pallas": False})
CAP = 1200
SHARDS = 2


class SpanRecorder:
    """Stands in for ``TraceAnnotation`` in both the fleet and the plane:
    records each span's opening and closing, with its keyword arguments."""

    def __init__(self):
        self.events = []  # ("open" | "close", name, kwargs)

    @contextlib.contextmanager
    def __call__(self, name, **kw):
        self.events.append(("open", name, kw))
        try:
            yield
        finally:
            self.events.append(("close", name, kw))

    def opens(self):
        return [(name, kw) for ev, name, kw in self.events if ev == "open"]


def _trace(n=960, key_space=300, seed=5):
    rng = np.random.default_rng(seed)
    keys = ((rng.zipf(1.2, size=n) - 1) % key_space).astype(np.int64)
    per_key = rng.integers(4, 12, size=key_space)
    return keys, per_key[keys].astype(np.int64)


def _sharded(keys, sizes):
    """Two shard caches over the trace (each ages every 160 accesses)."""
    pols = [REGISTRY.build(SPEC, CAP, **KW) for _ in range(SHARDS)]
    return fleet.FleetEngine.sharded(pols, keys, sizes)


def _sequential(keys, sizes):
    """The same cache driven alone by the ``device_full`` plane."""
    p = REGISTRY.build(SPEC, CAP, **KW)
    hits = [bool(h) for h in p.access_batch(keys, sizes)]
    p.sync_deferred()
    return p, hits


def _assert_sequential(m):
    p, hits = _sequential(m.keys, m.sizes)
    q = m.policy
    assert [bool(h) for h in m.hit_mask] == hits
    for f in ("accesses", "hits", "bytes_requested", "bytes_hit",
              "victims_examined", "admissions", "rejections", "evictions"):
        assert getattr(q.stats, f) == getattr(p.stats, f), f
    for pol in (p, q):
        pol.sketch.flush()
    assert np.array_equal(np.asarray(q.sketch.table), np.asarray(p.sketch.table))
    assert q.sketch.resets == p.sketch.resets
    assert list(q.window.items()) == list(p.window.items())
    assert q.main.export_rows() == p.main.export_rows()
    a, b = p._device_pipeline, q._device_pipeline
    assert (b.resyncs, b.uploads, b.chunk_calls, b.resync_reasons) == \
        (a.resyncs, a.uploads, a.chunk_calls, a.resync_reasons)


@pytest.fixture
def recorded(monkeypatch):
    """A sharded run with the recorder in both modules, and counting
    wrappers around the lane scatter and gather."""
    rec = SpanRecorder()
    monkeypatch.setattr(fleet, "_span", rec)
    monkeypatch.setattr(df, "_span", rec)
    moved = {"scattered": 0, "gathered": 0}
    scatter, gather = fleet._scatter_lanes, fleet._gather_lane

    def counted_scatter(*args):
        moved["scattered"] += len(args[10])  # the lane indices
        return scatter(*args)

    def counted_gather(*args):
        moved["gathered"] += 1
        return gather(*args)

    monkeypatch.setattr(fleet, "_scatter_lanes", counted_scatter)
    monkeypatch.setattr(fleet, "_gather_lane", counted_gather)
    keys, sizes = _trace()
    eng = _sharded(keys, sizes)
    eng.run()
    return eng, rec, moved


def _rounds(opens):
    """The spans of each round, split where a ``fleet.take`` opens; inner
    spans (resyncs, the plane's) stay in their round."""
    rounds = []
    for name, kw in opens:
        if name == TAKE:
            rounds.append([])
        if rounds:
            rounds[-1].append((name, kw))
    return rounds


def test_each_round_opens_its_phases_in_order(recorded):
    eng, rec, _ = recorded
    stack = []
    for ev, name, kw in rec.events:
        if ev == "open":
            stack.append((name, kw))
        else:
            assert stack.pop() == (name, kw)
    assert stack == []

    rounds = _rounds(rec.opens())
    # every round launches but the last, whose take finds every lane done
    assert len(rounds) == eng.launches + 1
    assert rounds[-1][0] == (TAKE, {"round": eng.launches})
    for k, spans in enumerate(rounds[:-1]):
        phases = [(n, kw) for n, kw in spans if n in ROUND]
        assert phases == [(n, {"round": k}) for n in ROUND]
        assert {kw["round"] for n, kw in spans if n.startswith("fleet.")} == {k}


def test_each_counted_resync_opens_one_span(recorded):
    eng, rec, _ = recorded
    ages = [(kw["lane"], kw["round"]) for name, kw in rec.opens()
            if name == RESYNC and kw["reason"] == "aging"]
    assert ages, "no lane reached its aging boundary"
    for m in eng.members:
        assert sum(lane == m.lane for lane, _ in ages) == \
            m.pipe.resync_reasons["aging"] > 0
    assert not any(name == RESYNC and kw["reason"] != "aging"
                   for name, kw in rec.opens())
    # an aging resync opens inside its round's take
    stack = []
    for ev, name, kw in rec.events:
        if ev == "close":
            stack.pop()
            continue
        if name == RESYNC:
            assert stack[-1] == (TAKE, {"round": kw["round"]})
        stack.append((name, kw))


def test_lane_counters_match_the_scatters_and_gathers(recorded):
    eng, _, moved = recorded
    assert eng.lane_uploads == moved["scattered"] >= SHARDS
    assert eng.lane_restores == moved["gathered"]
    # a gather for each aging replay and one per lane at release
    aging = sum(m.pipe.resync_reasons["aging"] for m in eng.members)
    assert eng.lane_restores == aging + SHARDS
    # each restored lane uploads again, bar the restores at release
    assert eng.lane_uploads == SHARDS + aging


def test_no_plane_wait_or_fetch_in_a_fleet_round(recorded):
    _, rec, _ = recorded
    plane = {name for name, _ in rec.opens() if name.startswith("plane.")}
    assert df.PLANE_SPANS[3] not in plane and df.PLANE_SPANS[4] not in plane
    assert plane <= {df.PLANE_SPANS[-1]}  # a mirror grow's resync at most


def test_recorded_members_equal_their_sequential_runs(recorded):
    eng, _, _ = recorded
    assert len(eng.members) == SHARDS
    for m in eng.members:
        assert m.policy.stats.accesses == len(m.keys) > 0
        _assert_sequential(m)


def test_a_tick_renorm_resyncs_inside_the_commit(monkeypatch):
    """With the renorm threshold lowered, every commit renormalizes its
    lane's stamps: a ``fleet.resync`` with ``reason=renorm`` inside
    ``fleet.commit``, uncounted as in the plane, and each member still
    equals its sequential run under the same threshold."""
    monkeypatch.setattr(df, "_TICK_RENORM", 40)
    rec = SpanRecorder()
    monkeypatch.setattr(fleet, "_span", rec)
    keys, sizes = _trace(n=320)
    eng = _sharded(keys, sizes)
    eng.run()
    stack, renorms = [], 0
    for ev, name, kw in rec.events:
        if ev == "close":
            stack.pop()
            continue
        if name == RESYNC and kw["reason"] == "renorm":
            assert stack[-1] == (COMMIT, {"round": kw["round"]})
            renorms += 1
        stack.append((name, kw))
    assert renorms > 0
    for m in eng.members:
        _assert_sequential(m)


def test_spans_on_a_profiler_host_plane(tmp_path):
    """A real ``jax.profiler`` trace holds all eight spans by name, each
    with its round index, on a host plane."""
    import jax
    from jax.profiler import ProfileData

    keys, sizes = _trace(n=480)
    eng = _sharded(keys, sizes)
    with jax.profiler.trace(str(tmp_path)):
        eng.run()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in fleet.FLEET_SPANS:
                        found.setdefault(ev.name, set()).add(dict(ev.stats).get("round"))
    assert set(found) == set(fleet.FLEET_SPANS)
    launched = set(range(eng.launches))
    for name in ROUND[1:]:
        assert found[name] == launched, name
    assert found[TAKE] == launched | {eng.launches}
