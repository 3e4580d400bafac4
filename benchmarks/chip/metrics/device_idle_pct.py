"""Share of the traced job's wall window in which no operation ran on the
device (1 - union of operation intervals / window), averaged over
chips."""


def read(ctx):
    if ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
