"""The fleet cell's readers, ``fleet_round_gap_us`` and ``fleet_access_us``:
their arithmetic on a trace built by hand, ``None`` on a trace without a
``_fleet_chunk`` program, and both on two rounds of ``msr2-slru-shard4``
traced on the chip."""

import types
from pathlib import Path

import pytest

import harness
import trace_reduce

BENCH = Path(__file__).resolve().parent.parent
DATA = Path(__file__).resolve().parent / "data"
READERS = ("fleet_round_gap_us", "fleet_access_us")


def _reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py", f"chipbench_metric_{name}")


def _ctx(trace, traced_accesses=1000):
    return types.SimpleNamespace(trace=trace, traced_accesses=traced_accesses,
                                 counters={}, log=lambda *a: None)


def _rounds(program="jit__fleet_chunk"):
    """Three fleet programs on the device at [1, 4], [6, 9] and [13, 16]
    seconds; a lane's scatter runs at [10, 11] inside the second gap."""
    modules = [(program, 1.0, 4.0), (program, 6.0, 9.0), (program, 13.0, 16.0),
               ("jit__scatter_lanes", 10.0, 11.0)]
    ops = [("while.1", 1.0, 4.0), ("while.1", 6.0, 9.0), ("while.1", 13.0, 16.0),
           ("scatter.2", 10.0, 11.0)]
    return trace_reduce.Trace(ops, modules, [("traced_window", 0.0, 20.0)])


def test_the_round_gap_is_the_median_idle_between_programs():
    # gaps 2 s and 4 s, less the scatter's 1 s: 2 and 3, median 2.5
    assert _reader("fleet_round_gap_us").read(_ctx(_rounds())) == pytest.approx(2.5e6)


def test_the_access_time_is_program_time_over_traced_accesses():
    assert _reader("fleet_access_us").read(_ctx(_rounds(), 3000)) == pytest.approx(3e3)


def test_programs_cut_by_the_stretch_do_not_count():
    t = _rounds()
    t.spans = [("traced_window", 2.0, 20.0)]  # the first program starts before
    assert _reader("fleet_round_gap_us").read(_ctx(t)) == pytest.approx(3e6)
    assert _reader("fleet_access_us").read(_ctx(t, 2)) == pytest.approx(3e6)


@pytest.mark.parametrize("name", READERS)
def test_no_fleet_program_reads_nothing(name):
    single = _rounds(program="jit__simulate_chunk")
    assert _reader(name).read(_ctx(single)) is None
    assert _reader(name).read(_ctx(None)) is None


def test_one_program_has_no_gap_and_no_accesses_no_time():
    t = _rounds()
    t.modules = t.modules[:1]
    assert _reader("fleet_round_gap_us").read(_ctx(t)) is None
    assert _reader("fleet_access_us").read(_ctx(t, 0)) is None


def test_the_cell_declares_both_readers():
    cell = harness.load_cell("msr2-slru-shard4")
    assert cell.traffic["shards"] == 4 and cell.chips == 1
    assert [m["name"] for m in cell.per_layer] == list(READERS)
    assert [m["name"] for m in cell.end_to_end] == ["accesses_per_s", "batch_p95_ms",
                                                    "setup_s"]


def test_a_fleet_trace_recorded_on_the_chip():
    """Two rounds of ``msr2-slru-shard4`` traced on one TPU v5e, reduced with
    the ``fleet.*`` spans kept: both readers find the chip's ``_fleet_chunk``
    programs, and the idle between the rounds falls inside the round's
    phases."""
    t = trace_reduce.load_json(DATA / "msr2-slru-shard4-v5e-trace.json.gz")
    assert t.aligned
    runs = t.modules_inside(("_fleet_chunk",))
    assert len(runs) == 2
    lo, hi = t.window
    gap = _reader("fleet_round_gap_us").read(_ctx(t))
    assert 0 < gap <= (runs[1][1] - runs[0][2]) * 1e6 + 1e-6
    traced = 2 * 4 * 256  # two rounds of at most 256 accesses a lane
    device = sum(e - s for _, s, e in runs)
    assert _reader("fleet_access_us").read(_ctx(t, traced)) == pytest.approx(device / traced * 1e6)
    phases = ("fleet.take", "fleet.upload", "fleet.pack", "fleet.enqueue", "fleet.wait",
              "fleet.fetch", "fleet.commit")
    for name in phases:
        assert sum(n == name and lo <= s and e <= hi for n, s, e in t.spans) == 2, name
    idle = t.idle_by_span()
    assert sum(idle.get(n, 0.0) for n in phases) >= 0.9 * sum(idle.values())
