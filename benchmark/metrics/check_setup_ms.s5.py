"""Check set-up: from the start of a check (span ``raft.check``) to the
start of its level loop (span ``raft.check.levels``), in ms, the mean
over the window's checks."""

from harness import spans


def read(ctx):
    if ctx.trace is None:
        return None
    loops = [s for s, _e in spans.clipped(ctx.trace, "raft.check.levels")]
    setups = []
    for s, e in spans.clipped(ctx.trace, "raft.check"):
        starts = [ls for ls in loops if s <= ls < e]
        if starts:
            setups.append(min(starts) - s)
    if not setups:
        return None
    return sum(setups) / len(setups) / 1e6
