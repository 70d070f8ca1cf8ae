"""How full the dispatcher's walk batches were over the window: walks
served over (batches dispatched x batch_max), from the server's
counters, in %."""


def read(ctx):
    served = ctx.stats1["served"] - ctx.stats0["served"]
    batches = ctx.stats1["batches"] - ctx.stats0["batches"]
    if batches <= 0:
        return None
    return 100.0 * served / (batches * ctx.batch_max)
