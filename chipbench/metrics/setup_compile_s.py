"""Seconds JAX spent compiling (or loading compiled programs) in set-up,
from its own compile events."""


def read(ctx):
    return ctx.setup_compile_s
