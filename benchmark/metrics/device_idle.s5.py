"""Share of the traced window in which no operation ran on the device."""

from harness import trace


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - trace.busy_s(ctx.trace) / trace.window_s(ctx.trace))
