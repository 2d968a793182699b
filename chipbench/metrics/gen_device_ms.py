"""Device milliseconds per step of the operations under the generator's
``jit(gen_fn)`` (generation and feature fetch); moves ``seeds_per_s``."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.groups_s["gen"]:
        return None
    return 1e3 * ctx.trace.groups_s["gen"] / ctx.traced_steps
