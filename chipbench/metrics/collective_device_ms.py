"""Device milliseconds per step of collective operations (all-to-all,
all-gather, all-reduce, collective-permute); moves ``seeds_per_s``.
Only a cell on several chips has any."""


def read(ctx):
    if ctx.trace is None or ctx.workers < 2 \
            or not ctx.trace.groups_s["collective"]:
        return None
    return 1e3 * ctx.trace.groups_s["collective"] / ctx.traced_steps
