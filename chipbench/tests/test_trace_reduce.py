"""The trace reduction's arithmetic, on a hand-made trace with known answers
and on many random intervals against a brute-force sweep."""

import gzip
import json
from pathlib import Path

import numpy as np
import pytest

import names
import trace_reduce

DATA = Path(__file__).resolve().parent / "data"


def _hand_made():
    ops = [("fusion", 1.0, 2.0), ("closed_call.7", 1.5, 2.5),
           ("copy", 4.0, 5.0), ("fusion", 7.0, 7.5), ("fusion", 11.0, 12.0)]
    modules = [("jit__simulate_chunk", 1.0, 2.5), ("jit__simulate_chunk", 4.0, 5.0),
               ("jit_other", 7.0, 7.5), ("jit__simulate_chunk", 9.5, 12.0)]
    spans = [("traced_window", 0.0, 10.0), ("batch", 0.5, 3.0), ("_dispatch", 0.6, 1.0),
             ("batch", 3.5, 9.0), ("_collect", 5.0, 6.5), ("ensure_host", 6.5, 8.0)]
    return trace_reduce.Trace(ops, modules, spans, details={
        "closed_call.7": '%closed_call.7 = (s32[4,128]{1,0}, s32[4,1]{1,0}) custom-call('
                         '%a, %b, %c), custom_call_target="tpu_custom_call"'})


def test_hand_made_trace():
    t = _hand_made()
    assert t.window == (0.0, 10.0)
    # busy: [1, 2.5] + [4, 5] + [7, 7.5]; the op at 11 lies outside
    assert t.busy_seconds() == pytest.approx(3.0)
    assert t.idle_share() == pytest.approx(0.7)
    assert t.op_seconds() == pytest.approx({"fusion": 1.5, "closed_call.7": 1.0,
                                            "copy": 1.0})
    assert [m[1] for m in t.modules_inside(names.CHUNK_PROGRAMS)] == [1.0, 4.0]
    assert names.cms_kernel_events(t, 4, 128) == [("closed_call.7", 1.5, 2.5)]
    assert names.cms_kernel_events(t, 4, 256) == []  # another table's kernel
    # idle: [0, 1], [2.5, 4], [5, 7], [7.5, 10]; each piece goes to the
    # innermost span open around it
    got = t.idle_by_span()
    assert got == pytest.approx({"between batches": 0.5 + 0.5 + 1.0,  # [0,.5] [3,3.5] [9,10]
                                 "batch": 0.1 + 0.5 + 0.5 + 1.0,  # [.5,.6] [2.5,3] [3.5,4] [8,9]
                                 "_dispatch": 0.4, "_collect": 1.5,
                                 "ensure_host": 0.5 + 0.5})
    assert sum(got.values()) == pytest.approx(10.0 - t.busy_seconds())
    b = t.breakdown()
    assert b["device_ops"][0] == ["fusion", pytest.approx(1.5)]
    assert b["idle_gaps"][0] == ["batch", pytest.approx(2.1)]


def test_events_match_by_their_details_too():
    t = _hand_made()
    t.ops.append(("custom-call.9", 2.6, 2.8))  # another Pallas kernel
    t.details["custom-call.9"] = ('%custom-call.9 = s32[8,512]{1,0} custom-call(%x), '
                                  'custom_call_target="tpu_custom_call"')
    t.modules.append(("XLA module 12", 5.5, 6.0))
    t.details["XLA module 12"] = "jit(_fleet_chunk)"
    assert names.cms_kernel_events(t, 4, 128) == [("closed_call.7", 1.5, 2.5)]
    assert [m[0] for m in t.modules_inside(names.CHUNK_PROGRAMS)] == [
        "jit__simulate_chunk", "jit__simulate_chunk", "XLA module 12"]


def test_json_round_trip(tmp_path):
    t = _hand_made()
    p = tmp_path / "t.json.gz"
    with gzip.open(p, "wt") as f:
        json.dump(t.to_json(), f)
    u = trace_reduce.load_json(p)
    assert u.busy_seconds() == t.busy_seconds()
    assert u.breakdown() == t.breakdown()


def _brute_busy(ops, lo, hi, step):
    """Busy time by sweeping the stretch on a fine grid."""
    grid = np.arange(lo, hi, step) + step / 2
    busy = np.zeros(len(grid), bool)
    for _, s, e in ops:
        busy[(grid >= s) & (grid < e)] = True
    return busy.sum() * step


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_many_overlapping_intervals_match_a_brute_force_sweep(seed):
    """Overlapping, nested and abutting device operations on a 1 us grid,
    the traced stretch cutting some of them."""
    rng = np.random.default_rng(seed)
    start = rng.integers(0, 20_000, 3000)
    ops = [("fusion" if k % 3 else "closed_call.7", s * 1e-6, (s + d) * 1e-6)
           for k, (s, d) in enumerate(zip(start.tolist(), rng.integers(1, 8, 3000).tolist()))]
    spans = [("traced_window", 1e-3, 19e-3)] + [
        ("batch", b * 1e-3, (b + 0.9) * 1e-3) for b in range(20)] + [
        ("_collect", (b + 0.5) * 1e-3, (b + 0.8) * 1e-3) for b in range(20)]
    t = trace_reduce.Trace(ops, [], spans, details=_hand_made().details)
    lo, hi = t.window
    busy = t.busy_seconds()
    assert busy == pytest.approx(_brute_busy(ops, lo, hi, 1e-6), rel=1e-6)
    assert t.idle_share() == pytest.approx(1 - busy / (hi - lo))
    idle = t.idle_by_span()
    assert sum(idle.values()) == pytest.approx(hi - lo - busy, rel=1e-9)
    assert set(idle) <= {"batch", "_collect", "between batches"}
    assert sum(t.op_seconds().values()) >= busy * (1 - 1e-9)  # ops overlap
    kernel = names.cms_kernel_events(t, 4, 128)
    assert kernel and all(lo <= s and e <= hi for _, s, e in kernel)


def test_a_device_clock_apart_from_the_host_falls_back_to_the_device_span():
    t = trace_reduce.Trace([("a", 100.0, 101.0), ("b", 102.0, 103.5)], [],
                           [("traced_window", 0.0, 10.0), ("batch", 1.0, 2.0)])
    assert not t.aligned
    assert t.window == (100.0, 103.5)
    assert t.idle_share() == pytest.approx(1.0 / 3.5)
    assert t.idle_by_span() == pytest.approx({"host clock not aligned": 1.0})
    assert _hand_made().aligned



def test_a_tpu_op_name_is_its_instruction():
    assert trace_reduce.split_op_name(
        '%closed_call.97 = (s32[4,65536]{1,0:T(4,128)}, s32[4,1]{1,0}) custom-call(%a), '
        'custom_call_target="tpu_custom_call"') == (
        "closed_call.97", '(s32[4,65536]{1,0:T(4,128)}, s32[4,1]{1,0}) custom-call(%a), '
        'custom_call_target="tpu_custom_call"')
    assert trace_reduce.split_op_name("jit__simulate_chunk_impl(20151438)") == (
        "jit__simulate_chunk_impl(20151438)", "")
    assert trace_reduce.split_op_name("fusion.3") == ("fusion.3", "")


def test_a_trace_recorded_on_the_chip():
    """Two window batches of ``msr2-slru`` traced on one TPU v5e (reduced
    with ``trace_reduce.py <xplane.pb> <out> --batches 2``): the readers
    find the chunk program and the CMS kernel by their names there."""
    import types

    import harness

    t = trace_reduce.load_json(DATA / "msr2-slru-v5e-trace.json.gz")
    assert t.aligned
    assert 0.0 < t.busy_seconds() < t.window_seconds()
    kernel = names.cms_kernel_events(t, 4, 65536)
    assert len(kernel) == 2 * 256  # one per scan step of each launch
    assert names.cms_kernel_events(t, 4, 131072) == []
    assert len(t.modules_inside(names.CHUNK_PROGRAMS)) == 2
    b = t.breakdown()
    assert "closed_call.97" in [n for n, _ in b["device_ops"]]
    assert set(t.idle_by_span()) <= {"batch", "_dispatch", "_collect", "ensure_host",
                                     "between batches"}
    cell = harness.load_cell("msr2-slru")
    ctx = types.SimpleNamespace(
        trace=t, counters={"sketch_width": 65536, "scan_len": 256, "lanes": 1,
                           "resyncs0": 3, "resyncs": 5},
        window_accesses=2 * 256, traced_accesses=2 * 256, config=cell.config,
        peaks=harness.peaks_for("TPU v5 lite"), log=lambda *a: None)
    got = {k: v["value"] for k, v in harness.read_metrics(cell, ctx).items()}
    assert 0.0 < got["device_idle_share"] < 1.0
    assert 0.0 < got["cms_kernel_us"] < got["scan_step_us"]
    assert 0.0 < got["cms_roofline"] < 100.0
