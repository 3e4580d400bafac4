"""Device time of the evaluator's batched predict over every client's test
split, as a share of the traced job's wall time.

The program jits its predict (and its batched client-state init) from
lambdas, so both run as ``jit__lambda`` programs; the predict is the one
of them that holds the most device time (it runs each eval over every
client's test split, the init once, on parameters only)."""

import collections

import trace_reduce

PROGRAM = "jit__lambda"


def read(ctx):
    by_hash = collections.Counter()
    for name, ns in ctx.trace.module_ns.items():
        if trace_reduce.base_name(name) == PROGRAM:
            by_hash[name] += ns
    if not by_hash or ctx.wall_s <= 0:
        return None
    eval_s = max(by_hash.values()) / 1e9 / ctx.trace.n_devices
    return 100.0 * eval_s / ctx.wall_s
