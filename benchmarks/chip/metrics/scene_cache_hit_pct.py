"""Share of scene lookups that hit the engine's scene cache in the window
(the system's own ``SceneCache`` hit and miss counters)."""


def read(ctx):
    if ctx.scene_cache is None:
        return None
    hits, misses = ctx.scene_cache
    if hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)
