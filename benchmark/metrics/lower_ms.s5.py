"""Host time JAX spent lowering programs, in ms per window check: its own
``lower_sharding_computation`` events on the checks' thread.  A program
traced or looked up anew in a check lowers here, and every table the
program captures as a literal is lowered with it."""

from harness import spans


def read(ctx):
    if ctx.trace is None or not ctx.window:
        return None
    lowered = spans.clipped(ctx.trace, "lower_sharding_computation")
    return spans.total_ns(lowered) / len(ctx.window) / 1e6
