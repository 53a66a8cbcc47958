"""Host filter time per batch: the system's ``filter`` spans in the window
(pruning, scene builds or lookups, stacking), summed, over the batches."""


def read(ctx):
    total = sum(t1 - t0 for name, t0, t1, _, _ in ctx.spans if name == "filter")
    if not ctx.batches or total == 0.0:
        return None
    return 1e3 * total / ctx.batches
