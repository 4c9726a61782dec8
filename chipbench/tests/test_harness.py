"""The harness finds a cell's configuration, traffic and metric readers by
name, so a cell is added with new files alone; it refuses to run without a
chip or without the program."""

import json
import os
import shutil
import statistics
import subprocess
import sys
import types

import pytest

from conftest import BENCH, ROOT

import harness


def _run(cwd, *args, env=None):
    return subprocess.run([sys.executable, "chipbench/run.py", *args], cwd=cwd,
                          capture_output=True, text=True, timeout=600,
                          env={**os.environ, **(env or {})})


@pytest.fixture
def copy(tmp_path):
    """The benchmark as a checkout would hold it, beside the program."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path


def test_a_cell_from_new_files_only(copy):
    (copy / "src").symlink_to(ROOT / "src")
    before = {p: p.read_bytes() for p in (copy / "chipbench").rglob("*") if p.is_file()}
    msr2 = json.loads((copy / "chipbench/traffic/msr2.json").read_text())
    msr1 = dict(msr2, **{"about": "msr1 class, 10% capacity, one cache",
                         "class": {"name": "msr1", "n_accesses": 700000,
                                   "n_objects": 280000, "zipf_alpha": 0.85,
                                   "p_recency": 0.35, "recency_scale": 2000,
                                   "size_kind": "clustered",
                                   "size_params": [[4096, 0.45], [65536, 0.35],
                                                   [262144, 0.15], [512, 0.05]],
                                   "one_hit_frac": 0.0}})
    (copy / "chipbench/traffic/msr1.json").write_text(json.dumps(msr1))
    cfg = json.loads((copy / "chipbench/configs/wtlfu-av-slru.json").read_text())
    cfg.update(name="wtlfu-iv-sampled-freq",
               spec="wtlfu-iv-sampled_frequency?data_plane=device_full&chunk=256&seed=0x5EED")
    cfg["reference"].update(admission="iv", main="sampled_frequency")
    (copy / "chipbench/configs/wtlfu-iv-sampled-freq.json").write_text(json.dumps(cfg))
    (copy / "chipbench/metrics/window_batches.py").write_text(
        "def read(ctx):\n    return ctx.window_accesses / 256\n")
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "wtlfu-iv-sampled-freq", "source": "test",
                             "file": "chipbench/configs/wtlfu-iv-sampled-freq.json",
                             "reduced": []})
    bench["workloads"].append({"name": "msr1-iv", "config": "wtlfu-iv-sampled-freq",
                               "traffic": "msr1", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "window_batches", "unit": "batches",
                               "better": "higher", "source": "program_counter",
                               "layer": "harness", "moves": "accesses_per_s",
                               "workloads": ["msr1-iv"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))
    for p, data in before.items():
        assert p.read_bytes() == data  # no file the benchmark had was edited

    r = _run(copy, "--workload", "msr1-iv", "--seed", "2147483659", "--seconds", "2",
             "--trace", "0", "--rehearse", "--scale", "0.02")
    assert r.returncode == 0, r.stderr[-3000:]
    result = json.loads(r.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, r.stderr[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"] == {}  # a rehearsal reports no metric
    assert list(result)[-1] == "checks"
    assert r.stderr.strip().splitlines()[-1].startswith("check ")

    cell = harness.load_cell("msr1-iv", copy)
    assert [m["name"] for m in cell.per_layer] == ["window_batches"]
    got = harness.read_metrics(cell, types.SimpleNamespace(window_accesses=512))
    assert got == {"window_batches": {"value": 2.0, "unit": "batches"}}


def test_the_shard_cell_left_for_later_rehearses(copy):
    """``msr2-slru-shard4`` (traffic ``msr2-shard4``) is not in BENCHMARK.json
    yet; added as one entry, it runs: four shard caches, one fleet round per
    batch, each shard checked against the reference and its partition."""
    (copy / "src").symlink_to(ROOT / "src")
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "msr2-slru-shard4", "config": "wtlfu-av-slru",
                               "traffic": "msr2-shard4", "chips": 1, "why": "test"})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))
    r = _run(copy, "--workload", "msr2-slru-shard4", "--seed", "2147483661",
             "--seconds", "2", "--trace", "0", "--rehearse", "--scale", "0.02")
    assert r.returncode == 0, r.stderr[-3000:]
    result = json.loads(r.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, r.stderr[-3000:]
    assert result["checks"]["partition_mismatch"] == {"value": 0, "limit": 0}


def test_no_chip_no_result():
    r = _run(ROOT, "--workload", "msr2-slru", "--seed", "1", "--seconds", "1",
             "--trace", "0", env={"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_benchmark_alone_does_not_run(copy):
    r = _run(copy, "--workload", "msr2-slru", "--seed", "1", "--seconds", "1",
             "--trace", "0", env={"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_spread_is_the_quartile_distance_over_the_median():
    import spread

    values = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    s = spread.spread(values)
    assert s["median"] == pytest.approx(10.05)
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert s["spread"] == pytest.approx((q3 - q1) / 10.05)


@pytest.mark.parametrize("config", ["wtlfu-av-slru", "wtlfu-qv-sampled-fsize"])
@pytest.mark.parametrize("entries", [64, 858, 2147, 6605, 8562, 12000, 16385])
def test_the_program_sketch_is_the_configured_one(config, entries):
    """The harness sizes the program's sketch to the configuration's rule
    (Caffeine's or Ristretto's), which the reference follows on its own."""
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    ref = harness.load_module(BENCH / "reference" / "wtlfu.py", "chipbench_reference_wtlfu")
    mine = ref.Cache(cfg["reference"], 1 << 30, entries).sketch
    rule = cfg["reference"]["sketch"]
    pol = harness.build_cache(cfg["spec"], 1 << 30, entries, rule, {"use_pallas": False})
    assert (pol.sketch.width, pol.sketch.sample_size) == (mine.width, mine.sample_size)
    assert mine.width >= rule["counters_per_entry"] * entries > mine.width // 2
    assert mine.sample_size == rule["sample_per_entry"] * entries


def test_a_declared_metric_that_reads_nothing_fails_the_run():
    """A per-layer metric the cell declares and whose reader finds nothing
    (the kernel renamed, say) is an error naming it, not a silent gap."""
    import trace_reduce

    cell = harness.load_cell("msr2-slru")
    cell.per_layer = [m for m in cell.per_layer if m["name"] == "cms_kernel_us"]
    trace = trace_reduce.Trace([("fusion", 1.0, 2.0)], [], [("traced_window", 0.0, 3.0)])
    logged = []
    ctx = types.SimpleNamespace(trace=trace, counters={"sketch_width": 65536},
                                config=cell.config, log=logged.append)
    with pytest.raises(harness.HarnessError, match="cms_kernel_us"):
        harness.read_metrics(cell, ctx)
    assert logged and logged[0].startswith("trace: 1 ops")
