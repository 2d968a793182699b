"""Distinct feature requests per request slot over the measured window:
``(hits + misses) / slots``; what dedup leaves the fetch to do; moves
``seeds_per_s``."""


def read(ctx):
    c = ctx.counters
    if not c["slots"]:
        return None
    return 100.0 * (c["hits"] + c["misses"]) / c["slots"]
