"""The batched ray-cast kernel's share of its roofline: the least time the
chip needs for the window's batches (``chipbench.work``, counted from each
query's real triangles) over the kernel's device time in the trace.

The least time is one chip's, for all the window's users; the kernel time
is summed over the chips that ran it.  On n chips the share is therefore
of their combined peak over the kernel's time on each."""

from chipbench.kernels import RAYCAST_BATCH
from chipbench.work import least_time, raycast_batch_work


def read(ctx):
    if ctx.tris is None or ctx.peaks is None:
        return None
    seconds, n = ctx.trace.kernel_seconds(RAYCAST_BATCH, *ctx.trace_window)
    if n == 0 or seconds <= 0.0:
        return None
    least = sum(
        least_time(*raycast_batch_work(ctx.n_users, tris), ctx.peaks)[0]
        for tris in ctx.tris
    )
    return 100.0 * least / seconds
