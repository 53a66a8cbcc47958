"""Host mask time per batch: the system's ``mask`` spans in the window
(``counts < k`` over the batch's ``[Q, N]`` counts), summed, over the
batches."""


def read(ctx):
    total = sum(t1 - t0 for name, t0, t1, _, _ in ctx.spans if name == "mask")
    if not ctx.batches or total == 0.0:
        return None
    return 1e3 * total / ctx.batches
