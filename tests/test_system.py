"""End-to-end behaviour tests for the paper's system: the full path from
trace -> policy -> metrics, the paper's headline claims as assertions, and
the cross-layer integrations (serving cache + data cache)."""

import importlib.util
import json
import pathlib

import numpy as np
import pytest

from repro.core import make_policy, simulate
from repro.traces import make_trace


class TestBenchTrajectory:
    """ISSUE 5 satellite: ``benchmarks/run.py overhead`` appends a dated
    entry to the BENCH_overhead.json trajectory (stable schema 2) instead
    of overwriting, migrating legacy schema-1 row lists in place."""

    def _module(self):
        path = pathlib.Path(__file__).parent.parent / "benchmarks" / "run.py"
        spec = importlib.util.spec_from_file_location("bench_run_under_test", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_appends_dated_entries(self, tmp_path):
        m = self._module()
        m.BENCH_OVERHEAD_PATH = tmp_path / "BENCH_overhead.json"
        rows = [{"policy": "x", "us_per_access": 2.0, "data_plane": "device_batched",
                 "trace": "t", "capacity": 1}]
        m.write_bench_overhead(rows)
        m.write_bench_overhead(rows)
        data = json.loads(m.BENCH_OVERHEAD_PATH.read_text())
        assert data["schema"] == 2
        assert len(data["history"]) == 2
        assert all(e["timestamp"] for e in data["history"])
        assert data["history"][-1]["rows"][0]["accesses_per_sec"] == 500000.0

    def test_migrates_legacy_row_list(self, tmp_path):
        m = self._module()
        m.BENCH_OVERHEAD_PATH = tmp_path / "BENCH_overhead.json"
        legacy = [{"policy": "old", "data_plane": None, "trace": "t",
                   "capacity": 9, "accesses_per_sec": 1.0}]
        m.BENCH_OVERHEAD_PATH.write_text(json.dumps(legacy))
        m.write_bench_overhead([{"policy": "new", "us_per_access": 1.0}])
        data = json.loads(m.BENCH_OVERHEAD_PATH.read_text())
        # ISSUE 7 satellite: the migrated entry is dated (file mtime), not null
        stamps = [e["timestamp"] for e in data["history"]]
        assert all(stamps), f"null timestamp persisted: {stamps}"
        assert data["history"][0]["rows"] == legacy
        assert data["history"][1]["rows"][0]["policy"] == "new"

    def test_null_timestamps_backfilled_on_load(self, tmp_path):
        """ISSUE 7 satellite regression: entries persisted with
        ``"timestamp": null`` (the pre-fix legacy migration) are
        backfilled from the file's mtime on load — UTC ISO-8601, parseable
        and ordered before the new append."""
        import datetime
        import os

        m = self._module()
        m.BENCH_OVERHEAD_PATH = tmp_path / "BENCH_overhead.json"
        stale = {"schema": 2, "history": [
            {"timestamp": None, "rows": [{"policy": "p", "data_plane": "d",
                                          "trace": "t", "capacity": 1,
                                          "accesses_per_sec": 5.0}]},
        ]}
        m.BENCH_OVERHEAD_PATH.write_text(json.dumps(stale))
        mtime = 1_700_000_000
        os.utime(m.BENCH_OVERHEAD_PATH, (mtime, mtime))
        m.write_bench_overhead([{"policy": "p", "data_plane": "d",
                                 "trace": "t", "capacity": 1,
                                 "us_per_access": 1.0}])
        data = json.loads(m.BENCH_OVERHEAD_PATH.read_text())
        t0, t1 = (e["timestamp"] for e in data["history"])
        assert t0 == datetime.datetime.fromtimestamp(
            mtime, datetime.timezone.utc).isoformat(timespec="seconds")
        assert datetime.datetime.fromisoformat(t0) < \
            datetime.datetime.fromisoformat(t1)

    def test_history_is_capped(self, tmp_path):
        m = self._module()
        m.BENCH_OVERHEAD_PATH = tmp_path / "BENCH_overhead.json"
        m.BENCH_HISTORY_MAX = 3
        for _ in range(5):
            m.write_bench_overhead([{"policy": "p", "us_per_access": 1.0}])
        data = json.loads(m.BENCH_OVERHEAD_PATH.read_text())
        assert len(data["history"]) == 3

    def _row(self, aps, policy="p", plane="device_full"):
        return {"policy": policy, "us_per_access": 1e6 / aps,
                "data_plane": plane, "trace": "t", "capacity": 1}

    def test_regression_flagged_in_entry(self, tmp_path):
        """ISSUE 7 satellite: a >15% accesses/sec drop vs the most recent
        prior run of the same (policy, data_plane, trace, capacity) row
        gets a visible marker in the appended JSON entry; smaller moves
        and improvements do not."""
        m = self._module()
        m.BENCH_OVERHEAD_PATH = tmp_path / "BENCH_overhead.json"
        m.write_bench_overhead([self._row(1000.0), self._row(1000.0, "q")])
        m.write_bench_overhead([self._row(900.0), self._row(1100.0, "q")])
        data = json.loads(m.BENCH_OVERHEAD_PATH.read_text())
        assert "regressions" not in data["history"][-1]  # -10%: tolerated
        assert all("regression" not in r for r in data["history"][-1]["rows"])
        m.write_bench_overhead([self._row(700.0), self._row(1100.0, "q")])
        data = json.loads(m.BENCH_OVERHEAD_PATH.read_text())
        entry = data["history"][-1]
        assert entry["regressions"] == 1
        flagged = [r for r in entry["rows"] if "regression" in r]
        assert [r["policy"] for r in flagged] == ["p"]
        reg = flagged[0]["regression"]
        assert reg["baseline_accesses_per_sec"] == 900.0  # most recent prior
        assert reg["change"] == pytest.approx(700.0 / 900.0 - 1.0, abs=1e-4)
        assert reg["baseline_timestamp"]

    def test_no_regression_across_hardware_backends(self, tmp_path):
        """ISSUE 8 satellite (failing before): a row timed on a fast
        accelerator must never become the baseline for a CPU run of the
        same policy — the row key includes the hardware backend, so the
        slower backend's first entry starts its own trajectory."""
        m = self._module()
        m.BENCH_OVERHEAD_PATH = tmp_path / "BENCH_overhead.json"
        m._hw_backend = lambda: "tpu"
        m.write_bench_overhead([self._row(100000.0)])
        m._hw_backend = lambda: "cpu"
        m.write_bench_overhead([self._row(1000.0)])  # 100x slower: new hw
        data = json.loads(m.BENCH_OVERHEAD_PATH.read_text())
        assert "regressions" not in data["history"][-1]
        assert all("regression" not in r
                   for r in data["history"][-1]["rows"])
        # same backend again IS gated (positive control)
        m.write_bench_overhead([self._row(500.0)])
        data = json.loads(m.BENCH_OVERHEAD_PATH.read_text())
        assert data["history"][-1]["regressions"] == 1

    def test_no_regression_across_drive_modes(self, tmp_path):
        """ISSUE 8 satellite (failing before): fleet rows amortize one
        wall-clock over many members, so a fleet row and a sequential row
        of the same policy are different measurements — the row key
        includes the drive mode and neither baselines the other."""
        m = self._module()
        m.BENCH_OVERHEAD_PATH = tmp_path / "BENCH_overhead.json"
        fleet = dict(self._row(10000.0), mode="fleet")
        m.write_bench_overhead([fleet])
        m.write_bench_overhead([self._row(1000.0)])  # sequential, 10x less
        data = json.loads(m.BENCH_OVERHEAD_PATH.read_text())
        assert "regressions" not in data["history"][-1]
        # the recorded rows carry the identity fields the key needs
        modes = [e["rows"][0].get("mode") for e in data["history"]]
        assert modes == ["fleet", None]
        assert all(e["rows"][0].get("backend")
                   for e in data["history"])
        # same mode again IS gated (positive control)
        m.write_bench_overhead([dict(self._row(1000.0), mode="fleet")])
        data = json.loads(m.BENCH_OVERHEAD_PATH.read_text())
        assert data["history"][-1]["regressions"] == 1

    def test_regression_strict_mode_fails_after_persisting(self, tmp_path,
                                                           monkeypatch):
        """REPRO_BENCH_STRICT turns a flagged regression into a failed run
        — but only after the flagged entry is written (the marker is the
        record; the failure is the enforcement)."""
        m = self._module()
        m.BENCH_OVERHEAD_PATH = tmp_path / "BENCH_overhead.json"
        m.write_bench_overhead([self._row(1000.0)])
        monkeypatch.setenv("REPRO_BENCH_STRICT", "1")
        with pytest.raises(SystemExit, match="regressed"):
            m.write_bench_overhead([self._row(500.0)])
        data = json.loads(m.BENCH_OVERHEAD_PATH.read_text())
        assert data["history"][-1]["regressions"] == 1
        # a clean run under strict mode appends normally
        m.write_bench_overhead([self._row(1000.0)])
        assert len(json.loads(
            m.BENCH_OVERHEAD_PATH.read_text())["history"]) == 3


class TestCompileCachePlacement:
    """``repro.compile_cache.use_compile_cache``: compiled programs land in
    ``JAX_COMPILATION_CACHE_DIR`` when it is set and nowhere else, and in
    ``<root>/.jax_cache`` when it is not. Each case runs in a fresh process,
    because JAX opens its persistent cache once per process."""

    SCRIPT = (
        "import sys, jax, jax.numpy as jnp\n"
        "from repro.compile_cache import use_compile_cache\n"
        "print(use_compile_cache(sys.argv[1]))\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()\n"
    )

    def _run(self, root, env_dir):
        import os
        import subprocess
        import sys

        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env["JAX_PLATFORMS"] = "cpu"
        src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
        env["PYTHONPATH"] = os.pathsep.join(
            [src] + [p for p in [env.get("PYTHONPATH")] if p])
        if env_dir is not None:
            env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
        out = subprocess.run([sys.executable, "-c", self.SCRIPT, str(root)],
                             env=env, capture_output=True, text=True,
                             timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        return out.stdout.strip().splitlines()[-1]

    def test_default_is_fixed_dir_in_root(self, tmp_path):
        used = self._run(tmp_path, None)
        assert used == str(tmp_path.resolve() / ".jax_cache")
        assert any((tmp_path / ".jax_cache").iterdir())

    def test_env_dir_wins_and_root_untouched(self, tmp_path):
        env_dir = tmp_path / "from_env"
        used = self._run(tmp_path, env_dir)
        assert used == str(env_dir)
        assert any(env_dir.iterdir())
        assert not (tmp_path / ".jax_cache").exists()


@pytest.fixture(scope="module")
def traces():
    return {n: make_trace(n, seed=0, scale=0.03) for n in ("msr2", "cdn1")}


def _run(name, trace, frac, **kw):
    cap = max(1, int(trace.total_object_bytes * frac))
    if "wtlfu" in name:
        kw.setdefault("expected_entries",
                      max(64, int(cap / trace.mean_object_size)))
    p = make_policy(name, cap, **kw)
    st = simulate(p, trace)
    return p, st


class TestPaperClaims:
    """The paper's section-5 findings as executable assertions (on
    synthetic paper-class traces; DESIGN.md §8)."""

    def test_av_beats_iv_and_lru_on_hit_ratio(self, traces):
        for tname, tr in traces.items():
            _, av = _run("wtlfu-av", tr, 0.02)
            _, iv = _run("wtlfu-iv", tr, 0.02)
            _, lru = _run("lru", tr, 0.02)
            assert av.hit_ratio > lru.hit_ratio, tname
            assert av.hit_ratio >= iv.hit_ratio - 0.01, tname

    def test_qv_strong_on_byte_hit_ratio(self, traces):
        tr = traces["cdn1"]
        _, qv = _run("wtlfu-qv", tr, 0.02)
        _, lru = _run("lru", tr, 0.02)
        assert qv.byte_hit_ratio > lru.byte_hit_ratio

    def test_early_pruning_reduces_victims_not_hit_ratio(self, traces):
        tr = traces["msr2"]
        _, pruned = _run("wtlfu-av", tr, 0.01, early_pruning=True)
        _, full = _run("wtlfu-av", tr, 0.01, early_pruning=False)
        assert pruned.victims_per_access < full.victims_per_access / 1.5
        assert abs(pruned.hit_ratio - full.hit_ratio) < 0.03

    def test_adaptsize_underutilizes_large_caches(self, traces):
        tr = traces["cdn1"]
        ads, st = _run("adaptsize", tr, 0.9)
        av, _ = _run("wtlfu-av", tr, 0.9)
        assert ads.used_bytes() < 0.6 * ads.capacity
        assert av.used_bytes() > 0.8 * av.capacity

    def test_av_cheaper_than_lhd_and_lrb(self, traces):
        tr = traces["cdn1"].slice(20_000)
        _, av = _run("wtlfu-av", tr, 0.01)
        _, lhd = _run("lhd", tr, 0.01)
        _, lrb = _run("lrb", tr, 0.01)
        assert av.wall_seconds < lhd.wall_seconds * 1.5
        assert av.wall_seconds < lrb.wall_seconds

    def test_belady_upper_bounds_everyone(self, traces):
        tr = traces["msr2"].slice(30_000)
        cap = int(tr.total_object_bytes * 0.02)
        opt = simulate(make_policy("belady", cap, trace=tr), tr)
        for name in ("lru", "wtlfu-av", "gdsf"):
            _, st = _run(name, tr, 0.02)
            assert opt.hit_ratio >= st.hit_ratio - 0.02, name


class TestCrossLayerIntegration:
    def test_same_policy_object_drives_all_layers(self):
        """One policy implementation serves the simulator, the serving
        prefix cache and the data shard cache."""
        from repro.serving import PrefixCache, PrefixCacheConfig
        from repro.training.data import ShardCache

        pc = PrefixCache(PrefixCacheConfig(
            capacity_bytes=1 << 16, block_size=4, bytes_per_token=16,
            policy="wtlfu-av"))
        sc = ShardCache(1 << 16, policy="wtlfu-av")
        assert type(pc.policy).__name__ == "SizeAwareWTinyLFU"
        assert type(sc.policy).__name__ == "SizeAwareWTinyLFU"

    def test_policy_stats_flow_to_serving_metrics(self):
        from repro.serving import PrefixCache, PrefixCacheConfig

        pc = PrefixCache(PrefixCacheConfig(
            capacity_bytes=1 << 16, block_size=4, bytes_per_token=16))
        p = list(range(8))
        pc.lookup(p)
        pc.offer(p)
        pc.lookup(p)
        s = pc.stats()
        assert s["request_hit_ratio"] == 0.5
        assert s["token_hit_ratio"] > 0
