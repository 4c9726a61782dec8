"""The host plane's own spans (``plane.*``, recorded by the program inside
``DeviceFullSimulationPlane``) and the idle split by phase that they allow.

``trace_reduce.load`` keeps only the spans in ``SPAN_NAMES``, which does not
name these yet; a trace that holds them (built here by hand, or reduced from
a chip trace with the names added) splits the device's idle time into the
launch's phases through ``Trace.idle_by_span`` as it is.
"""

from pathlib import Path

import pytest

import names
import trace_reduce

DATA = Path(__file__).resolve().parent / "data"

#: the plane's spans, as the program names them
LAUNCH_SIDE = ("plane.prepare", "plane.pack", "plane.enqueue")
COLLECT_SIDE = ("plane.wait", "plane.fetch", "plane.commit")


def _per_launch(idle: dict, side, launches: int) -> float:
    return sum(idle.get(n, 0.0) for n in side) / launches


def _two_launches():
    """Two batches of one launch each; every phase of the second takes
    twice as long. Program runs on the device at [2, 7] and [12, 17]."""
    spans = [("traced_window", 0.0, 20.0)]
    modules, ops = [], []
    for k, t0 in enumerate((0.0, 10.0)):
        f = 1 + k
        spans += [("batch", t0, t0 + 10.0), ("plane.prepare", t0 + 0.1, t0 + 0.2),
                  ("_dispatch", t0 + 0.2, t0 + 0.4 + 0.8 * f),
                  ("plane.pack", t0 + 0.3, t0 + 0.3 + 0.6 * f),
                  ("plane.enqueue", t0 + 0.3 + 0.6 * f, t0 + 0.3 + 0.8 * f),
                  ("_collect", t0 + 2.1, t0 + 7.1 + 1.2 * f),
                  ("plane.wait", t0 + 2.1, t0 + 7.0 + 0.25 * f),
                  ("plane.fetch", t0 + 7.0 + 0.25 * f, t0 + 7.0 + 1.0 * f),
                  ("plane.commit", t0 + 7.0 + 1.0 * f, t0 + 7.0 + 1.2 * f)]
        modules.append(("jit__simulate_chunk", t0 + 2.0, t0 + 7.0))
        ops.append(("while.1", t0 + 2.0, t0 + 7.0))
    return trace_reduce.Trace(ops, modules, spans)


def test_idle_splits_into_the_plane_phases():
    t = _two_launches()
    launches = len(t.modules_inside(names.CHUNK_PROGRAMS))
    assert launches == 2
    idle = t.idle_by_span()
    assert sum(idle.values()) == pytest.approx(20.0 - 10.0)
    # each phase's idle: launch 1 plus launch 2 (twice as long)
    assert idle["plane.prepare"] == pytest.approx(0.2)
    assert idle["plane.pack"] == pytest.approx(0.6 + 1.2)
    assert idle["plane.enqueue"] == pytest.approx(0.2 + 0.4)
    assert idle["plane.wait"] == pytest.approx(0.25 + 0.5)
    assert idle["plane.fetch"] == pytest.approx(0.75 + 1.5)
    assert idle["plane.commit"] == pytest.approx(0.2 + 0.4)
    assert _per_launch(idle, LAUNCH_SIDE, launches) == pytest.approx(1.3)
    assert _per_launch(idle, COLLECT_SIDE, launches) == pytest.approx(1.8)
    # what the outside spans still hold: the Python between the phases
    assert idle["_dispatch"] == pytest.approx(4 * 0.1)
    assert idle["_collect"] == pytest.approx(2 * 0.1)
    assert idle["batch"] == pytest.approx(0.1 + 0.8 + 1.7 + 0.1 + 0.5)
    gaps = [n for n, _ in t.breakdown()["idle_gaps"]]
    assert gaps[:2] == ["batch", "plane.fetch"]


def test_no_split_where_the_clocks_are_apart():
    t = _two_launches()
    t.ops = [(n, s + 100.0, e + 100.0) for n, s, e in t.ops]
    t.modules = [(n, s + 100.0, e + 100.0) for n, s, e in t.modules]
    assert not t.aligned
    assert set(t.idle_by_span()) == {"host clock not aligned"}


def test_a_plane_trace_recorded_on_the_chip():
    """Two window batches of ``msr2-slru`` traced on one TPU v5e, reduced
    with the ``plane.*`` spans kept: each launch's phases are there, the
    idle splits into them, the outside spans keep at most a tenth of it,
    and the kernel carries its own name."""
    t = trace_reduce.load_json(DATA / "msr2-slru-v5e-plane-trace.json.gz")
    assert t.aligned
    launches = len(t.modules_inside(names.CHUNK_PROGRAMS))
    assert launches >= 1
    lo, hi = t.window
    count = {}
    for n, s, e in t.spans:
        if lo <= s and e <= hi:
            count[n] = count.get(n, 0) + 1
    for n in LAUNCH_SIDE[1:] + COLLECT_SIDE:
        assert count.get(n, 0) >= launches, n
    idle = t.idle_by_span()
    plane = sum(idle.get(n, 0.0) for n in LAUNCH_SIDE + COLLECT_SIDE)
    assert plane > 0.0
    assert idle.get("_dispatch", 0.0) + idle.get("_collect", 0.0) <= 0.1 * plane
    kernel = names.cms_kernel_events(t, 4, 65536)
    assert kernel and {n.split(".")[0] for n, _, _ in kernel} == {"cms_update_estimate"}
