"""The whole step's share of the chips' peak: the model's matrix-product
FLOPs per seed (``costs.model_flops_per_seed``, forward and the backward
operations training needs) times the measured window's seeds per second,
over the cell's chips times the peak FLOP/s; moves ``seeds_per_s``."""
from chipbench import costs


def read(ctx):
    m = ctx.cfg["model"]
    f = costs.model_flops_per_seed(ctx.fanouts, m["gcn_in_dim"],
                                   m["gcn_hidden"], m["n_classes"])
    seeds_per_s = (ctx.window_steps * ctx.workers * ctx.seeds_per_worker
                   / ctx.window_s)
    peak = ctx.workers * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * (f["forward"] + f["backward"]) * seeds_per_s / peak
