"""Share of the devices' idle time in the window during which no span of
the system was open on any host thread: the ``repro/<span>`` annotations
the system writes into the profiler's trace while tracing is on, against
the intervals in which no device ran an operation, both on the trace's own
clock.  ``None`` where
the window holds no such annotation (a system that writes none)."""

from chipbench.xplane import merge, overlap_ns

PREFIX = "repro/"


def read(ctx):
    lo, hi = ctx.trace_window
    marks = merge(
        (max(e.start_ns, lo), min(e.end_ns, hi))
        for e in ctx.trace.host
        if e.name.startswith(PREFIX) and e.end_ns > lo and e.start_ns < hi
    )
    if not marks:
        return None
    gaps = ctx.trace.idle_gaps(lo, hi)
    idle = sum(e - s for s, e in gaps)
    if idle <= 0.0:
        return 0.0
    return 100.0 * (idle - overlap_ns(gaps, marks)) / idle
