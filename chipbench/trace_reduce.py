"""Reduce a profiler trace to the numbers the benchmark reports.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes. From it this module
keeps three kinds of interval, all in seconds on the trace's own clock:

* device operations: the events of each TPU plane's ``XLA Ops`` line, each
  named by its HLO instruction's name (the rest of the instruction is kept
  as its details);
* device programs: the events of its ``XLA Modules`` line;
* host spans: the ``TraceAnnotation`` events the benchmark records
  (:data:`SPAN_NAMES`), ``traced_window`` marking the traced stretch.

and from them computes busy time (the union of operation intervals inside
the traced stretch), per-operation device time, and idle time by the host
span that was open around it. :meth:`Trace.to_json` / :func:`from_json` keep a
reduced trace small enough to commit, which the tests run the arithmetic
on.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os

import numpy as np

SPAN_NAMES = ("traced_window", "batch", "ensure_host", "_dispatch", "_collect")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def split_op_name(name: str) -> tuple[str, str]:
    """A TPU trace names a device operation by its whole HLO instruction,
    ``%closed_call.97 = (s32[4,65536]{1,0}, ...) custom-call(...), ...``:
    the instruction's name, and the rest as its details."""
    head, sep, rest = name.partition(" = ")
    if not sep or " " in head:
        return name, ""
    return head.lstrip("%"), rest


def find_xplane(trace_dir) -> str:
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {paths}")
    return paths[0]


def _intervals(events) -> np.ndarray:
    if not events:
        return np.zeros((0, 2))
    a = np.asarray([(s, e) for _, s, e in events], np.float64)
    return a[np.argsort(a[:, 0], kind="stable")]


def union(iv: np.ndarray) -> np.ndarray:
    """Merge sorted ``[n, 2]`` intervals into disjoint ones."""
    if len(iv) == 0:
        return iv
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.flatnonzero(np.r_[new[1:], True])
    return np.stack([starts, ends[last]], axis=1)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(iv) == 0:
        return iv
    c = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], axis=1)
    return c[c[:, 1] > c[:, 0]]


@dataclasses.dataclass
class Trace:
    ops: list  # (name, start_s, end_s) device operations
    modules: list  # (name, start_s, end_s) device program executions
    spans: list  # (name, start_s, end_s) host spans
    devices: int = 1
    #: per device op or program name, the text of its string stats (the
    #: framework op path, the HLO text): what a reader matches beside the name
    details: dict = dataclasses.field(default_factory=dict)

    def named(self, events, patterns) -> list:
        """The events whose name or details contain any of ``patterns``."""
        hit = {}
        out = []
        for ev in events:
            n = ev[0]
            if n not in hit:
                text = n + " " + self.details.get(n, "")
                hit[n] = any(p in text for p in patterns)
            if hit[n]:
                out.append(ev)
        return out

    @property
    def window(self) -> tuple[float, float]:
        """The traced stretch: the host's ``traced_window`` span, or, where
        no device operation falls in it (the device's clock not aligned to
        the host's), the first to the last device operation."""
        w = [(s, e) for n, s, e in self.spans if n == "traced_window"]
        if len(w) != 1:
            raise ValueError(f"expected one traced_window span, found {len(w)}")
        lo, hi = w[0]
        if self.ops and not any(e > lo and s < hi for _, s, e in self.ops):
            return min(s for _, s, _ in self.ops), max(e for _, _, e in self.ops)
        return lo, hi

    @property
    def aligned(self) -> bool:
        """Whether device and host events share one clock."""
        return self.window == next(
            (s, e) for n, s, e in self.spans if n == "traced_window")

    def window_seconds(self) -> float:
        lo, hi = self.window
        return hi - lo

    def _inside(self, events) -> list:
        lo, hi = self.window
        return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]

    def busy_intervals(self) -> np.ndarray:
        lo, hi = self.window
        return clip(union(_intervals(self.ops)), lo, hi)

    def busy_seconds(self) -> float:
        """Seconds of the traced stretch in which a device operation ran,
        averaged over the devices traced."""
        b = self.busy_intervals()
        return float((b[:, 1] - b[:, 0]).sum()) / max(1, self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_seconds() / self.window_seconds()

    def op_seconds(self) -> dict:
        out: dict = {}
        for n, s, e in self._inside(self.ops):
            out[n] = out.get(n, 0.0) + (e - s)
        return out

    def ops_inside(self) -> list:
        """Device operations that ran wholly inside the traced stretch."""
        lo, hi = self.window
        return [ev for ev in self.ops if ev[1] >= lo and ev[2] <= hi]

    def modules_inside(self, match) -> list:
        """Program executions wholly inside the traced stretch whose name
        or details contain any of ``match``."""
        lo, hi = self.window
        return self.named([ev for ev in self.modules if ev[1] >= lo and ev[2] <= hi],
                          match)

    def idle_by_span(self) -> dict:
        """Idle seconds of the traced stretch by what the host was doing:
        each idle piece goes to the innermost host span around it (the
        shortest), or to ``between batches`` where none is."""
        lo, hi = self.window
        busy = self.busy_intervals()
        if not self.aligned:
            return {"host clock not aligned": hi - lo - float((busy[:, 1] - busy[:, 0]).sum())}
        spans = sorted(((e - s, s, e, n) for n, s, e in self.spans
                        if n != "traced_window"), reverse=True)
        bounds = np.unique(np.clip(
            np.asarray([lo, hi] + [x for _, s, e, _ in spans for x in (s, e)]), lo, hi))
        label = np.full(len(bounds) - 1, len(spans))
        for k, (_, s, e, _) in enumerate(spans):  # inner spans overwrite outer
            label[np.searchsorted(bounds, s):np.searchsorted(bounds, e)] = k
        cuts = np.unique(np.concatenate([bounds, busy.ravel()]))
        mid = 0.5 * (cuts[:-1] + cuts[1:])
        j = np.searchsorted(busy[:, 0], mid, side="right") - 1
        idle = ~((j >= 0) & (mid < busy[np.maximum(j, 0), 1])) if len(busy) else \
            np.ones(len(mid), bool)
        lab = label[np.searchsorted(bounds, mid, side="right") - 1]
        secs = np.bincount(lab[idle], weights=np.diff(cuts)[idle],
                           minlength=len(spans) + 1)
        names = [n for *_, n in spans] + ["between batches"]
        out: dict = {}
        for k in np.flatnonzero(secs):
            out[names[k]] = out.get(names[k], 0.0) + float(secs[k])
        return out

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span().items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}

    def excerpt(self, batches: int) -> "Trace":
        """The trace cut to its traced stretch's first ``batches`` ``batch``
        spans: small enough to commit, with the events as recorded."""
        if not self.aligned:
            raise ValueError("device and host clocks apart: no excerpt by host spans")
        lo, hi = self.window
        ends = sorted(e for n, s, e in self.spans if n == "batch" and s >= lo and e <= hi)
        hi = ends[min(batches, len(ends)) - 1]

        def keep(events):
            return [ev for ev in events if ev[2] > lo and ev[1] < hi]

        ops, modules = keep(self.ops), keep(self.modules)
        spans = [("traced_window", lo, hi)] + [
            ev for ev in keep(self.spans) if ev[0] != "traced_window"]
        names = {n for n, _, _ in ops + modules}
        return Trace(ops, modules, spans, self.devices,
                     {n: d for n, d in self.details.items() if n in names})

    def to_json(self) -> dict:
        return {"devices": self.devices, "ops": self.ops, "modules": self.modules,
                "spans": self.spans, "details": self.details}


def from_json(obj: dict) -> Trace:
    return Trace([tuple(x) for x in obj["ops"]], [tuple(x) for x in obj["modules"]],
                 [tuple(x) for x in obj["spans"]], obj.get("devices", 1),
                 obj.get("details", {}))


def load_json(path) -> Trace:
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rt") as f:
        return from_json(json.load(f))


def load(path) -> Trace:
    """Read an ``.xplane.pb`` (with JAX's own reader)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops, modules, spans = [], [], []
    details: dict = {}
    devices = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            devices += 1
            for line in plane.lines:
                dest = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dest is None:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    name, text = split_op_name(ev.name)
                    dest.append((name, s, s + ev.duration_ns * 1e-9))
                    if name not in details:
                        # the heads of the instruction and of each string
                        # stat: shapes and custom-call target lie there, a
                        # kernel's payload after
                        details[name] = " ".join(
                            [text[:2000]] + [v[:1000] for _, v in ev.stats
                                             if isinstance(v, str)]).strip()
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPAN_NAMES:
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name, s, s + ev.duration_ns * 1e-9))
    return Trace(ops, modules, spans, max(1, devices), details)


def main(argv=None) -> int:
    """Reduce a recorded ``.xplane.pb`` to a JSON excerpt and print what it
    holds: ``python3 chipbench/trace_reduce.py <xplane.pb> <out.json.gz>
    [--batches N]``."""
    import argparse

    ap = argparse.ArgumentParser(description=main.__doc__.splitlines()[0])
    ap.add_argument("xplane")
    ap.add_argument("out")
    ap.add_argument("--batches", type=int, default=3)
    args = ap.parse_args(argv)
    t = load(args.xplane).excerpt(args.batches)
    with gzip.open(args.out, "wt") as f:
        json.dump(t.to_json(), f)
    print(json.dumps({"ops": len(t.ops), "modules": len(t.modules), "spans": len(t.spans),
                      "window_s": t.window_seconds(), "busy_s": t.busy_seconds(),
                      "module_names": sorted({n for n, _, _ in t.modules}),
                      **t.breakdown()}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
