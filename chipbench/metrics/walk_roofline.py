"""The walk program's share of the HBM roofline, in %: the least bytes
the window's walks need (counted by the reference from each walk's
frontier) at peak bandwidth, over the walk program's device time in the
trace."""
from chipbench import trace


def read(ctx):
    if ctx.trace is None or not ctx.walk_bytes or ctx.peaks is None:
        return None
    seconds = ctx.trace.modules.get(trace.WALK_PROGRAM, 0.0)
    if seconds <= 0:
        return None
    return 100.0 * trace.roofline_share(
        ctx.walk_bytes, seconds, ctx.peaks["hbm_bytes_per_s"])
