"""Host time the program's TickBuilder spends drawing, padding and staging
batches (engine ``stats["host_build_s"]``, a span the program times
itself), per global iteration folded in the traced job."""


def read(ctx):
    if not ctx.iters:
        return None
    return ctx.stats["host_build_s"] / ctx.iters * 1e6
