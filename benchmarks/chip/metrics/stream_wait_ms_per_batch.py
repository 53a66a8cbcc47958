"""Consumer wait per batch in ``stream``: the system's ``stream.wait`` spans
in the window (the verifying thread blocked on the producer's next
filtered batch), summed, over the batches."""


def read(ctx):
    total = sum(t1 - t0 for name, t0, t1, _, _ in ctx.spans if name == "stream.wait")
    if not ctx.batches or total == 0.0:
        return None
    return 1e3 * total / ctx.batches
