"""Seconds in the writer's ``serve.seal`` span (sealing a generation and
acking its group) per generation sealed until the server stopped, in
ms."""
from chipbench import spans


def read(ctx):
    seconds = spans.span_seconds(ctx, "serve.seal")
    seals = ctx.final_stats["seals"] - ctx.stats0["seals"]
    if seconds is None or seals <= 0:
        return None
    return 1e3 * seconds / seals
