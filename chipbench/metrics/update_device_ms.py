"""Device time of the fused update program per batch acked in the
window, from the trace.  The program's jitted function is named ``fn``
(``slot_update.ops._jit_fused``), so its module is ``jit_fn``."""

PROGRAM = "jit_fn"


def read(ctx):
    if ctx.trace is None:
        return None
    acked = sum(u.status == "served" for u in ctx.updates)
    seconds = ctx.trace.modules.get(PROGRAM, 0.0)
    if acked == 0 or seconds <= 0:
        return None
    return 1e3 * seconds / acked
