"""Host time to hand the superstep program to the device (span
``raft.superstep.dispatch``: a retrace, lowering or cache load lands
here), in ms, the mean over the window's supersteps."""

from harness import spans


def read(ctx):
    if ctx.trace is None:
        return None
    ds = spans.clipped(ctx.trace, "raft.superstep.dispatch")
    if not ds:
        return None
    return spans.total_ns(ds) / len(ds) / 1e6
