"""The fleet's host round as the chip sees it: the median device-idle time
between consecutive ``_fleet_chunk`` programs wholly inside the traced
stretch. Device work between them (a lane's scatter or gather) is busy
time, not gap."""

import statistics

import trace_reduce

FLEET_PROGRAM = ("_fleet_chunk",)


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    runs = sorted(t.modules_inside(FLEET_PROGRAM), key=lambda ev: ev[1])
    if len(runs) < 2:
        return None
    busy = t.busy_intervals()
    gaps = []
    for (_, _, end), (_, start, _) in zip(runs, runs[1:]):
        b = trace_reduce.clip(busy, end, start)
        gaps.append(start - end - float((b[:, 1] - b[:, 0]).sum()))
    return statistics.median(gaps) * 1e6
