"""Mean arrivals folded per scheduler tick in the traced job, from the
program's per-tick telemetry records (a count)."""


def read(ctx):
    if not ctx.ticks:
        return None
    return sum(n for _, n, _ in ctx.ticks) / len(ctx.ticks)
