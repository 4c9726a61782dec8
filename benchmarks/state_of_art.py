"""Paper Figures 11 & 12: AV(SLRU) and QV(SLRU) vs the state of the art
(GDSF, AdaptSize, LHD, LRB) on hit-ratio and byte-hit-ratio, plus LRU as the
cross-framework sanity baseline and offline Belady as the upper reference.

The largest cache fraction plays the paper's "practically unbounded" 1TB/10TB
role, where AdaptSize's admission pathology (§5.2) shows as a flat hit-ratio
and low cache utilization."""

from __future__ import annotations

from .common import (PAPER_TRACES, emit, get_trace, run_policies_fleet,
                     run_policy, sequential_mode)

POLICIES = ("lru", "wtlfu-av", "wtlfu-qv", "gdsf", "adaptsize", "lhd", "lrb", "belady")
FRACS = (0.001, 0.01, 0.1, 0.5, 0.95)  # last two ~ unbounded regime


def main(traces=PAPER_TRACES, fracs=FRACS, policies=POLICIES) -> list[dict]:
    rows = []
    for tname in traces:
        tr = get_trace(tname)
        caps = {frac: max(1, int(tr.total_object_bytes * frac))
                for frac in fracs}
        # the W-TinyLFU grid (every policy x capacity for this trace) rides
        # one fleet; the comparison policies keep the scalar loop
        fleet = {}
        wtlfu = [(pol, frac) for frac in fracs for pol in policies
                 if pol.startswith("wtlfu")]
        if wtlfu and not sequential_mode():
            try:
                frows = run_policies_fleet(
                    [(pol, caps[frac]) for pol, frac in wtlfu], tr)
                fleet = dict(zip(wtlfu, frows))
            except ValueError as e:
                # e.g. trace objects past the device_full int32 size
                # bound — this trace keeps the per-policy loop
                print(f"# fleet path unavailable for {tname}: {e}")
        for frac in fracs:
            for pol in policies:
                r = fleet.get((pol, frac))
                if r is None:
                    r = run_policy(pol, tr, caps[frac])
                r["frac"] = frac
                rows.append(r)
    emit("state_of_art", rows, derived_key="hit_ratio")
    return rows


if __name__ == "__main__":
    main()
