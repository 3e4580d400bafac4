"""Device time of the fused megastep program (``jit_megastep``: a window
of ticks: local rounds, fold, write-back) in the traced job, averaged
over chips, per global iteration folded."""

PROGRAM = "jit_megastep"


def read(ctx):
    s = ctx.trace.module_s(PROGRAM) / ctx.trace.n_devices
    if s <= 0 or not ctx.iters:
        return None
    return s / ctx.iters * 1e6
