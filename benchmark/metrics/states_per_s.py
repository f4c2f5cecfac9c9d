"""Distinct states of every check in the window, over the window's length."""


def read(ctx):
    return sum(r.distinct for r in ctx.window if r.error is None) / ctx.window_s
