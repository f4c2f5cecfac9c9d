"""The device allocator's ``peak_bytes_in_use`` after the window, in GB."""


def read(ctx):
    if ctx.memory_peak_bytes is None:
        return None
    return ctx.memory_peak_bytes / 1e9
