"""Share of the traced window in which no operation ran on the device,
averaged over the cell's devices; moves ``seeds_per_s``."""


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.idle_pct
