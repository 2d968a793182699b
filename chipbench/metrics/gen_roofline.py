"""Generation's share of its HBM roofline, in percent: the least bytes
one worker's generation must move in a step (``costs.gen_min_bytes``,
from shapes and the traced steps' own distinct-id counts) over the
chip's HBM bandwidth, divided by the device time generation took per
step; moves ``seeds_per_s``."""
from chipbench import costs


def read(ctx):
    if ctx.trace is None or not ctx.trace.groups_s["gen"]:
        return None
    need = costs.gen_min_bytes(ctx.fanouts, ctx.seeds_per_worker,
                               ctx.workers, ctx.cfg["dataset"]["feat_dim"],
                               ctx.traced_distinct)
    t_min = need / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * t_min / (ctx.trace.groups_s["gen"] / ctx.traced_steps)
