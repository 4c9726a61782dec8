"""Host resyncs of the device_full plane (sketch aging and mirror growth),
per million accesses of the window: the plane's own ``resyncs`` counter."""


def read(ctx):
    c = ctx.counters
    if c.get("resyncs") is None or c.get("resyncs0") is None or not ctx.window_accesses:
        return None
    return (c["resyncs"] - c["resyncs0"]) / (ctx.window_accesses / 1e6)
