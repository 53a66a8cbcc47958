"""Scene build time per batch: the system's ``scene.build`` spans in the
window (pruning, occluder triangles and their padding, on scene-cache
misses and direct builds), summed, over the batches."""


def read(ctx):
    total = sum(t1 - t0 for name, t0, t1, _, _ in ctx.spans if name == "scene.build")
    if not ctx.batches or total == 0.0:
        return None
    return 1e3 * total / ctx.batches
