"""Walks answered inside the window, over the window's seconds."""


def read(ctx):
    done = [w for w in ctx.walks if w.status == "served" and w.done <= ctx.end]
    return len(done) / ctx.seconds
