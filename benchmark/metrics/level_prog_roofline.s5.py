"""The level programs' share of the HBM roofline for the work BFS needs.

The work is counted from the checks' semantic counts alone: one 64-bit
fingerprint probe per generated state and one insert per new state,
8 B x (generated + distinct), whatever implements it.  The least time the
chip could take for it, at the peak HBM bandwidth of ``peaks.json``, is
divided by the device time of the level programs (``programs.json``) in
the window.
"""

from harness import trace


def read(ctx):
    if ctx.trace is None:
        return None
    prog_s = trace.program_s(ctx.trace, ctx.level_programs)
    if prog_s <= 0:
        return None
    nbytes = sum(8 * (r.generated + r.distinct)
                 for r in ctx.window if r.error is None)
    return 100.0 * nbytes / ctx.peak["hbm_bytes_per_s"] / prog_s
