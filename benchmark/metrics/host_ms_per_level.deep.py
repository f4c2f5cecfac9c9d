"""Host time per BFS level in the window: the hub's level wall time less
the fetch wait it ledgered (time the host waited on the device)."""


def read(ctx):
    c = ctx.in_window
    if c.levels <= 0:
        return None
    return 1000.0 * (c.level_s - c.fetch_wait_s) / c.levels
