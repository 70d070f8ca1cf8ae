"""Device time of the walk program per batch dispatched in the window,
from the trace and the server's batch counter."""
from chipbench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    batches = ctx.stats1["batches"] - ctx.stats0["batches"]
    seconds = ctx.trace.modules.get(trace.WALK_PROGRAM, 0.0)
    if batches <= 0 or seconds <= 0:
        return None
    return 1e3 * seconds / batches
