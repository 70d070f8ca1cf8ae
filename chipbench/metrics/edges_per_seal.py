"""Edge ops acked per generation sealed, over the window and its close,
from the clients' acks and the server's seal counter."""


def read(ctx):
    seals = ctx.final_stats["seals"] - ctx.stats0["seals"]
    ops = sum(u.n_ops for u in ctx.updates if u.status == "served")
    return ops / seals if seals > 0 and ops > 0 else None
