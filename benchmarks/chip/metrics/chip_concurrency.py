"""How many chips ran at once while any ran: each chip's busy seconds in
the window, summed over the chips that ran an operation, over the seconds
of the union of their busy intervals.  1.0 where one chip runs at a time,
n where n chips are busy over the same intervals."""


def read(ctx):
    lo, hi = ctx.trace_window
    union = sum(e - s for s, e in ctx.trace.busy_union(lo, hi))
    if union <= 0.0:
        return None
    per_chip = sum(
        e - s for d in ctx.trace.devices for s, e in ctx.trace.busy(d, lo, hi)
    )
    return per_chip / union
