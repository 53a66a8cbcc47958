"""Device time of the batched ray-cast kernel per batch: the durations of
its events in the profiler trace inside the window, summed over devices,
over the batches."""

from chipbench.kernels import RAYCAST_BATCH


def read(ctx):
    seconds, n = ctx.trace.kernel_seconds(RAYCAST_BATCH, *ctx.trace_window)
    if n == 0 or not ctx.batches:
        return None
    return 1e3 * seconds / ctx.batches
