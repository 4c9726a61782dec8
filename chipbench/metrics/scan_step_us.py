"""Chunk-program time per scan step: device time of the chunk programs that
ran wholly inside the traced stretch, over their scan steps times lanes
(padded steps included)."""

import names


def read(ctx):
    t = ctx.trace
    scan_len, lanes = ctx.counters.get("scan_len"), ctx.counters.get("lanes")
    if t is None or not scan_len or not lanes:
        return None
    runs = t.modules_inside(names.CHUNK_PROGRAMS)
    if not runs:
        return None
    device_s = sum(e - s for _, s, e in runs)
    return device_s / (len(runs) * scan_len * lanes) * 1e6
