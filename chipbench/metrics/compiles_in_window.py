"""Programs compiled, or loaded from the compile cache, inside the
window, counted by a ``jax.monitoring`` listener."""


def read(ctx):
    return ctx.compiles_in_window
