"""Compilations inside the measured window: ``/jax/core/compile/`` events
that ``jax.monitoring`` reported, plus the deltas of the system's
``compile.count{fn}`` counters.  Set-up warms every shape, so this reads 0
unless something retraces or recompiles while serving."""


def read(ctx):
    return float(sum(ctx.compiles.values()))
