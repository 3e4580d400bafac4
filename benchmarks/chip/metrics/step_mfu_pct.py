"""Model FLOPs of the traced job's global iterations (forward and backward
of each local round, from shapes: ``flops.py``) per wall second of the
job, over the chips' bf16 peak."""

import flops


def read(ctx):
    if not ctx.iters or ctx.wall_s <= 0:
        return None
    work = flops.train_flops_per_iter(ctx.cell.config, ctx.cell.traffic)
    rate = work * ctx.iters / ctx.wall_s
    return 100.0 * rate / (ctx.chips * ctx.peak["flops_per_s"])
