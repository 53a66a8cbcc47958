"""Verify dispatch time per batch: the system's ``verify`` spans in the
window (copies to the device, the kernel, the copy back and the unsort),
summed, over the batches."""


def read(ctx):
    total = sum(t1 - t0 for name, t0, t1, _, _ in ctx.spans if name == "verify")
    if not ctx.batches or total == 0.0:
        return None
    return 1e3 * total / ctx.batches
