"""Device time of the Pallas ``linear_scan`` kernel (the associative fold's
recurrence h_s = a_s * h_{s-1} + b_s) on the first chip in the traced job,
per global iteration folded.

A call is a Mosaic kernel whose HLO text has two ``f32[1,S,C]`` operands
(a and b) and the results ``(f32[1,S,C], f32[1,1,C])`` (h and the last
state).  The kernel gets a time and no share of a roofline: the compiler
keeps a, b and h on chip (memory space 1), so their bytes over the HBM
bandwidth bound nothing, and the peak table has no published on-chip
bandwidth."""

import re

ARRAY = re.compile(r"f32\[([\d,]*)\]")


def _shapes(text):
    return [tuple(int(d) for d in dims.split(",") if d)
            for dims in ARRAY.findall(text)]


def is_call(text):
    """Whether the kernel call ``text`` is a ``linear_scan``."""
    head, _, rest = text.partition(" custom-call(")
    results = _shapes(head)
    operands = _shapes(rest.split("), custom_call_target", 1)[0])
    if len(results) != 2 or len(operands) != 2:
        return False
    h, last = results
    return (len(h) == 3 and h[0] == 1 and last == (1, 1, h[2])
            and all(shape == h for shape in operands))


def read(ctx):
    ns = sum(k.duration_ns for k in ctx.trace.kernels if is_call(k.text))
    if ns <= 0 or not ctx.iters:
        return None
    return ns / 1e3 / ctx.iters
