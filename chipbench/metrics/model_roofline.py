"""The model layer's share of its matrix-unit roofline, in percent: the
family's matrix-product FLOPs per seed (``flops_per_seed``, forward and
the backward operations training needs) times one worker's seeds per
step, over the chip's peak FLOP/s, divided by the device time per step
of the step program's operations outside the generator (the model's
forward and backward pass and the optimizer); moves ``seeds_per_s``."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.groups_s["model"]:
        return None
    f = ctx.family.flops_per_seed(ctx.fanouts, ctx.cfg["model"])
    t_min = ((f["forward"] + f["backward"]) * ctx.seeds_per_worker
             / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * t_min / (ctx.trace.groups_s["model"] / ctx.traced_steps)
