"""Share of distinct feature requests the hot-node cache served over the
measured window: ``n_cache_hits / (n_cache_hits + n_cache_misses)``,
summed over steps and workers; moves ``seeds_per_s``."""


def read(ctx):
    c = ctx.counters
    total = c["hits"] + c["misses"]
    if not total:
        return None
    return 100.0 * c["hits"] / total
