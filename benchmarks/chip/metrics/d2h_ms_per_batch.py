"""Copy-back time per batch: the system's ``verify.d2h`` spans in the
window (the ``[Q, N]`` counts from the device to the host, once the device
result is ready), summed, over the batches."""


def read(ctx):
    total = sum(t1 - t0 for name, t0, t1, _, _ in ctx.spans if name == "verify.d2h")
    if not ctx.batches or total == 0.0:
        return None
    return 1e3 * total / ctx.batches
