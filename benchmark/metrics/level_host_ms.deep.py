"""The level loop's host time per committed level, in ms: the window's
level loops (span ``raft.check.levels``) less the ledgered fetches
inside them (span ``raft.fetch``, time the host waited on the device),
over the depths the window's checks reached.  Check set-up and finish
lie outside the loop span."""

from harness import spans


def read(ctx):
    if ctx.trace is None:
        return None
    loops = spans.clipped(ctx.trace, "raft.check.levels")
    levels = sum(r.depth or 0 for r in ctx.window)
    if not loops or levels <= 0:
        return None
    fetches = spans.within(spans.clipped(ctx.trace, "raft.fetch"), loops)
    return (spans.total_ns(loops) - spans.total_ns(fetches)) / levels / 1e6
