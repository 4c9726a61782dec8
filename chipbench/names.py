"""Names of the program's device work as they appear in a profiler trace:
what the metric readers look for. A name that no event carries makes its
readers find nothing, and the run then fails naming them."""

#: the chunk programs, by their module names: one cache
#: (``_simulate_chunk_impl``) and one bucket of shard caches (``_fleet_chunk``)
CHUNK_PROGRAMS = ("_simulate_chunk", "_fleet_chunk")
#: the custom-call target every Pallas kernel lowers to
PALLAS_TARGET = "tpu_custom_call"


def sketch_shape(rows: int, width: int) -> str:
    """The sketch table's shape as HLO prints it."""
    return f"s32[{rows},{width}]"


def cms_kernel_events(trace, rows: int, width: int) -> list:
    """The fused CMS update+estimate kernel's events: the Pallas calls that
    return the sketch table, ``rows`` x ``width`` int32. The kernel carries
    no name of its own in the compiled program (its instruction is a
    ``closed_call``), so it is known by its target and the table's shape;
    another Pallas kernel of the chunk program does not return the table."""
    if trace is None or not width:
        return []
    shape = sketch_shape(rows, width)
    return [ev for ev in trace.named(trace.ops_inside(), (PALLAS_TARGET,))
            if shape in trace.details.get(ev[0], "")]


def describe(trace, limit: int = 4) -> str:
    """What the trace holds under the names above, for a run whose readers
    found nothing: module names, and the Pallas calls with the head of
    their details."""
    if trace is None:
        return "no trace"
    mods = sorted({n for n, _, _ in trace.modules})[:limit]
    calls = sorted({n for n, _, _ in trace.named(trace.ops, (PALLAS_TARGET,))})[:limit]
    return (f"{len(trace.ops)} ops, modules {mods}, Pallas calls "
            f"{[(n, trace.details.get(n, '')[:160]) for n in calls]}")
