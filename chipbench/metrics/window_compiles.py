"""Programs compiled or loaded inside the measured window (should be 0);
moves ``seeds_per_s``."""


def read(ctx):
    return ctx.window_compiles
