"""Fleet program time per resolved access: device time of the
``_fleet_chunk`` programs wholly inside the traced stretch, over the
accesses the traced rounds resolved (ragged lanes and padded steps count
as cost)."""

FLEET_PROGRAM = ("_fleet_chunk",)


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.traced_accesses:
        return None
    runs = t.modules_inside(FLEET_PROGRAM)
    if not runs:
        return None
    return sum(e - s for _, s, e in runs) / ctx.traced_accesses * 1e6
