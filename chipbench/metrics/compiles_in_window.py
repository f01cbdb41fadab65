"""Programs compiled or loaded from the cache during the window, from
JAX's backend-compile events.  A steady window compiles none."""


def read(ctx):
    return ctx.window_compiles
