"""The window over the checks it completed: the mean time to a verdict."""


def read(ctx):
    return ctx.window_s / len(ctx.window)
