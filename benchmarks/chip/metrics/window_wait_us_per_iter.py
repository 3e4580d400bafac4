"""Time the dispatching thread waits for the next window (the program's
``repro.window.wait`` spans, summed in ``stats["window_wait_s"]``), per
global iteration folded in the traced job: near zero while the prefetch
thread's staging hides behind the device.  None for a program without
the span."""


def read(ctx):
    wait_s = ctx.stats.get("window_wait_s")
    if wait_s is None or not ctx.iters:
        return None
    return wait_s / ctx.iters * 1e6
