"""Share of the CMS kernel's roofline: the least time the sketch work of the
traced accesses needs on this chip, over the kernel's device time there.

The work per access is the update of one key: ``rows`` counters read and
written at int32 (the estimate the scan step asks of the same call is not
used, and the kernel's sweep of the whole table is not needed work). It is
bound by memory bandwidth; its integer operations (one add and one min per
counter) would take a million times less at the chip's integer peak."""

import names


def read(ctx):
    rows = ctx.config["reference"]["sketch"]["rows"]
    width = ctx.counters.get("sketch_width")
    events = names.cms_kernel_events(ctx.trace, rows, width)
    if not events or not ctx.traced_accesses:
        return None
    ctx.log(f"cms kernel: sweeps {2 * rows * width * 4} table bytes per call "
            f"(read and write, width {width}); needs {2 * rows * 4} per access")
    need_bytes = ctx.traced_accesses * 2 * rows * 4
    least_s = need_bytes / ctx.peaks["hbm_bytes_per_s"]
    kernel_s = sum(e - s for _, s, e in events)
    return least_s / kernel_s * 100.0
