"""Device idle share: 1 - (union of device operation intervals) / (traced
stretch), from the profiler trace of a steady stretch of the window."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.ops:
        return None
    return t.idle_share()
