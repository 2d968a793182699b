"""Device milliseconds per step of the step program's operations outside
the generator: the model's forward and backward pass and the optimizer;
moves ``seeds_per_s``."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.groups_s["model"]:
        return None
    return 1e3 * ctx.trace.groups_s["model"] / ctx.traced_steps
