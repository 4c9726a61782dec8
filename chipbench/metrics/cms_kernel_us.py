"""Fused CMS update+estimate kernel: device time of its events inside the
traced stretch, over their number."""

import names


def read(ctx):
    events = names.cms_kernel_events(ctx.trace, ctx.config["reference"]["sketch"]["rows"],
                                     ctx.counters.get("sketch_width"))
    if not events:
        return None
    return sum(e - s for _, s, e in events) / len(events) * 1e6
