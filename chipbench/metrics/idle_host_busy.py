"""Share of the traced window in which the device was idle while a
server thread was inside a work span (not waiting for a request), in %:
the idle time the host, not the load, is to blame for.  Nothing without
program spans in the trace."""


def read(ctx):
    if not getattr(ctx.trace, "spans", None):
        return None
    return 100.0 * ctx.trace.idle_host_busy_s / ctx.trace.window_s
