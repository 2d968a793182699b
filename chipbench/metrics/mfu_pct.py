"""The whole step's share of the chips' peak: the model's matrix-product
FLOPs per seed (the family's ``flops_per_seed``, forward and the backward
operations training needs) times the measured window's seeds per second,
over the cell's chips times the peak FLOP/s; moves ``seeds_per_s``."""


def read(ctx):
    f = ctx.family.flops_per_seed(ctx.fanouts, ctx.cfg["model"])
    seeds_per_s = (ctx.window_steps * ctx.workers * ctx.seeds_per_worker
                   / ctx.window_s)
    peak = ctx.workers * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * (f["forward"] + f["backward"]) * seeds_per_s / peak
