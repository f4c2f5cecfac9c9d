"""Process start to the window's start: JAX init, cache loads, warm-up."""


def read(ctx):
    return ctx.setup_s
