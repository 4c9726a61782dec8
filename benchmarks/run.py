"""Benchmark entry point: one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [bench ...]``

Emits ``name,us_per_call,derived`` CSV rows and writes JSON to
``benchmarks/results/``. Scale with REPRO_BENCH_SCALE (default 0.08).

Running the ``overhead`` bench additionally updates ``BENCH_overhead.json``
at the repo root: a **trajectory** file — each run APPENDS one dated entry
of compact ``(policy, data_plane, trace, accesses_per_sec)`` rows instead
of overwriting, so throughput across PRs and nightly runs is
machine-readable without parsing the full
``benchmarks/results/overhead.json`` (nightly CI uploads the trajectory
as an artifact). Stable schema::

    {"schema": 2,
     "history": [{"timestamp": "<UTC ISO-8601>", "rows": [...]}]}

Legacy single-run files (a bare row list, schema 1) are migrated in place
as one entry; entries persisted without a timestamp are backfilled from
the file's mtime on load, so every entry is dated. History is capped at
the most recent ``BENCH_HISTORY_MAX`` entries. Each append compares its
rows against the trajectory baseline: a >15% accesses/sec drop for any
``(policy, data_plane, trace, capacity, backend, mode)`` row flags the
row in the written entry and — under ``REPRO_BENCH_STRICT=1`` (the
nightly bench jobs) — fails the run. The key includes the hardware
backend and the drive mode (fleet vs sequential) so a CPU row
landing after an accelerator row, or a per-policy-loop row after a fleet
row, can never raise a false regression.

Flags: ``--quick`` (smoke tier), ``--sequential`` (bypass the
fleet sweep path in state_of_art/robustness/overhead; also honored as
``REPRO_BENCH_SEQUENTIAL=1``).
"""

from __future__ import annotations

import datetime
import json
import os
import pathlib
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH_OVERHEAD_PATH = _ROOT / "BENCH_overhead.json"
BENCH_SERVING_PATH = _ROOT / "BENCH_serving.json"
#: Trajectory length cap: nightly appends one entry per run.
BENCH_HISTORY_MAX = 180
#: Fractional accesses/sec drop (vs the most recent prior run of the same
#: row) that flags a perf regression in the appended entry.
BENCH_REGRESSION_TOLERANCE = 0.15


def _utc_stamp(epoch: "float | None" = None) -> str:
    """UTC ISO-8601 with second precision, e.g. ``2026-08-08T12:00:00+00:00``."""
    dt = (datetime.datetime.now(datetime.timezone.utc) if epoch is None
          else datetime.datetime.fromtimestamp(epoch, datetime.timezone.utc))
    return dt.isoformat(timespec="seconds")


def _load_bench_history(path: pathlib.Path) -> "list[dict]":
    try:
        with open(path) as f:
            prior = json.load(f)
        mtime = path.stat().st_mtime
    except (OSError, ValueError):
        return []
    if isinstance(prior, list):  # schema 1: one overwritten row list
        history = [{"timestamp": None, "rows": prior}] if prior else []
    elif isinstance(prior, dict) and isinstance(prior.get("history"), list):
        history = prior["history"]
    else:
        return []
    # Entries written before timestamps existed (and schema-1 migrations)
    # carry ``null``: backfill from the file's last-modified time so every
    # persisted entry is dated — the regression gate needs a real ordering.
    for entry in history:
        if isinstance(entry, dict) and entry.get("timestamp") is None:
            entry["timestamp"] = _utc_stamp(mtime)
    return history


#: Throughput metrics the regression gate understands, in lookup order
#: (overhead rows carry the first, serving rows the second).
_GATED_METRICS = ("accesses_per_sec", "requests_per_sec")


def _hw_backend() -> str:
    """The hardware identity recorded on trajectory rows: a CPU run must
    never be gated against a faster accelerator baseline."""
    try:
        import jax

        return jax.default_backend()
    except Exception:
        return "cpu"


def _row_key(r: dict) -> tuple:
    # full row identity: benchmark config (policy/admission/trace/capacity),
    # data plane, hardware backend, and drive mode (fleet vs sequential) —
    # a row may only be compared against a prior run of the SAME thing
    return tuple(r.get(k) for k in ("policy", "data_plane", "admission",
                                    "arch", "trace", "capacity",
                                    "backend", "mode"))


def _row_metric(r: dict) -> "tuple[str, float] | None":
    for m in _GATED_METRICS:
        v = r.get(m)
        if v:
            return m, v
    return None


def _flag_regressions(history: "list[dict]") -> "list[dict]":
    """Compare the newest entry's rows against the most recent prior run
    of the same ``(policy, data_plane, ...)`` row. A
    ``> BENCH_REGRESSION_TOLERANCE`` throughput drop gets a visible
    ``"regression"`` marker on the row (and a ``"regressions"`` count on
    the entry) — the append-only log is an enforced perf contract, not
    just a record. Returns the flagged rows."""
    if len(history) < 2:
        return []
    baseline: "dict[tuple, tuple]" = {}
    for entry in history[:-1]:
        for r in entry.get("rows", ()):
            metric = _row_metric(r)
            if r.get("policy") and metric:
                baseline[_row_key(r)] = (metric[1], entry.get("timestamp"))
    flagged = []
    new = history[-1]
    for r in new.get("rows", ()):
        metric = _row_metric(r)
        base = baseline.get(_row_key(r))
        if metric is None or base is None:
            continue
        name, value = metric
        base_value, base_ts = base
        change = value / base_value - 1.0
        if change < -BENCH_REGRESSION_TOLERANCE:
            r["regression"] = {
                f"baseline_{name}": base_value,
                "baseline_timestamp": base_ts,
                "change": round(change, 4),
            }
            flagged.append(r)
    if flagged:
        new["regressions"] = len(flagged)
    return flagged


def _append_trajectory(path: pathlib.Path, rows: "list[dict]") -> None:
    """Append one dated entry of condensed rows to a schema-2 trajectory
    file, capping history at BENCH_HISTORY_MAX entries. Rows regressing
    >15% vs their trajectory baseline are flagged in the written entry;
    with ``REPRO_BENCH_STRICT`` set, flagged rows also fail the run
    (after persisting the entry, so the marker is never lost)."""
    history = _load_bench_history(path)
    history.append({"timestamp": _utc_stamp(), "rows": rows})
    history = history[-BENCH_HISTORY_MAX:]
    flagged = _flag_regressions(history)
    with open(path, "w") as f:
        json.dump({"schema": 2, "history": history}, f, indent=1)
    for r in flagged:
        reg = r["regression"]
        base = {k: v for k, v in reg.items()
                if k.startswith("baseline_") and k != "baseline_timestamp"}
        print(
            f"# PERF REGRESSION {r.get('policy')}/{r.get('data_plane')} on "
            f"{r.get('trace')}: {reg['change']:+.1%} vs {base} "
            f"({reg['baseline_timestamp']})",
            file=sys.stderr, flush=True)
    if flagged and os.environ.get("REPRO_BENCH_STRICT"):
        raise SystemExit(
            f"{len(flagged)} benchmark row(s) regressed "
            f">{BENCH_REGRESSION_TOLERANCE:.0%} vs the {path.name} "
            "trajectory baseline (rows are flagged in the appended entry)")


def write_bench_overhead(rows: "list[dict]") -> None:
    """Append this run's condensed overhead rows to the perf trajectory."""
    backend = _hw_backend()
    out = [
        {
            "policy": r["policy"],
            "data_plane": r.get("data_plane"),
            "trace": r.get("trace"),
            "capacity": r.get("capacity"),
            "backend": backend,
            "mode": r.get("mode"),
            "accesses_per_sec": round(1e6 / max(r["us_per_access"], 1e-9), 1),
        }
        for r in rows
        if r.get("policy") and r.get("us_per_access")
    ]
    _append_trajectory(BENCH_OVERHEAD_PATH, out)


def write_bench_serving(rows: "list[dict]") -> None:
    """Append this run's serving load-benchmark rows to BENCH_serving.json."""
    keep = (
        "policy", "admission", "arch", "trace", "n_requests",
        "requests_per_sec", "decision_p50_ms", "decision_p99_ms",
        "max_queue_depth", "request_hit_ratio", "token_hit_ratio",
        "byte_hit_ratio",
    )
    backend = _hw_backend()
    out = [{**{k: r.get(k) for k in keep}, "backend": backend}
           for r in rows if r.get("bench") == "serving_load"]
    _append_trajectory(BENCH_SERVING_PATH, out)


def main() -> None:
    from repro.compile_cache import use_compile_cache

    from . import filter_variants, overhead, pruning, robustness, state_of_art, trace_stats

    use_compile_cache(_ROOT)

    benches = {
        "trace_stats": trace_stats.main,  # Table 1 / Fig 8
        "pruning": pruning.main,  # Fig 7
        "filter_variants": filter_variants.main,  # Figs 9-10
        "state_of_art": state_of_art.main,  # Figs 11-12 (end-to-end)
        "robustness": robustness.main,  # Figs 11-12 (hit ratio over time)
        "overhead": overhead.main,  # Fig 13 / Table 2
    }
    try:  # serving integration benches (need the serving stack)
        from . import serving_cache

        benches["serving_cache"] = serving_cache.main
        benches["serving"] = serving_cache.load_main  # end-to-end load bench
    except ImportError:
        pass
    try:  # kernel micro-benchmarks (interpret mode)
        from . import kernel_bench

        benches["kernel_bench"] = kernel_bench.main
    except ImportError:
        pass

    args = sys.argv[1:]
    if "--quick" in args:  # smoke tier: tiny fixed-seed configs
        args.remove("--quick")
        os.environ["REPRO_BENCH_QUICK"] = "1"
    if "--sequential" in args:  # escape hatch: per-policy loops instead of
        args.remove("--sequential")  # the fleet sweep path
        os.environ["REPRO_BENCH_SEQUENTIAL"] = "1"
    selected = args or list(benches)
    print("name,us_per_call,derived")
    for name in selected:
        t0 = time.perf_counter()
        rows = benches[name]()
        if name == "overhead" and rows:
            write_bench_overhead(rows)
            print(f"# appended trajectory entry to {BENCH_OVERHEAD_PATH}", flush=True)
        if name == "serving" and rows:
            write_bench_serving(rows)
            print(f"# appended trajectory entry to {BENCH_SERVING_PATH}", flush=True)
        print(f"# {name} done in {time.perf_counter() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
