"""Host time of the per-shard dispatch per batch: the part of the
system's ``shard-verify`` spans (one a shard and batch: the kernel's
enqueue, the wait, the slab's copy back, its scatter into the host's
``[N + 1, Q]`` array and the shard's member count) in which no chip ran an
operation, read from the ``repro/shard-verify`` annotations and the device
operations on the trace's own clock, summed over the window, over the
batches.  The shards run one after another, so every chip waits through
this time."""

from chipbench.xplane import merge, overlap_ns

ANNOTATION = "repro/shard-verify"


def read(ctx):
    lo, hi = ctx.trace_window
    spans = merge(
        (max(e.start_ns, lo), min(e.end_ns, hi))
        for e in ctx.trace.host
        if e.name == ANNOTATION and e.end_ns > lo and e.start_ns < hi
    )
    if not spans or not ctx.batches:
        return None
    return overlap_ns(ctx.trace.idle_gaps(lo, hi), spans) / 1e6 / ctx.batches
