"""Host time of the dispatcher per walk batch: the seconds in its
``serve.walk.prepare`` (shedding, seed rows, the ``[B, V]`` stack, the
program's enqueue) and ``serve.walk.fetch`` (device to host, resolving
the tickets) spans, over the batches dispatched until the server
stopped."""
from chipbench import spans


def read(ctx):
    seconds = spans.span_seconds(ctx, "serve.walk.prepare", "serve.walk.fetch")
    batches = ctx.final_stats["batches"] - ctx.stats0["batches"]
    if seconds is None or batches <= 0:
        return None
    return 1e3 * seconds / batches
