"""Reduce a JAX profiler trace to what the per-layer metrics read.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.  In
it, each chip is a plane named ``/device:TPU:<n>``: its ``XLA Modules``
line holds one event per program execution, named ``<program>(<hash>)``,
and its ``XLA Ops`` line one per operation, named by the operation's HLO
text (a ``while`` op spans the ops of its body).  The host plane holds the
``TraceAnnotation`` spans.  Times are nanoseconds on one clock.

* ``busy``: the union of the operation intervals of each chip inside the
  traced window (the host span named by ``span``), averaged over chips;
* ``gaps``: the idle intervals of the first chip in that window, each
  labelled with the innermost benchmark span the host was in;
* device time by program and by operation (``%<op>.<n>``);
* every Mosaic kernel call (``custom_call_target="tpu_custom_call"``) of the
  first chip with its HLO text and duration, for the kernels' metrics.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'

Interval = Tuple[int, int]


def base_name(name: str) -> str:
    """A program's name without the ``(<hash>)`` the trace appends."""
    return re.sub(r"\(\d+\)$", "", name)


def op_name(text: str) -> str:
    """``%while.242`` of an operation event's HLO text."""
    return text.split(" = ", 1)[0]


def union(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    """The union of ``intervals`` clipped to [lo, hi], sorted, disjoint."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def gaps_of(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


@dataclasses.dataclass
class OpEvent:
    text: str  # the operation's HLO text
    duration_ns: int


@dataclasses.dataclass
class TraceSummary:
    window: Interval
    n_devices: int
    busy_ns: float  # mean over devices
    module_ns: Dict[str, float]  # by full program name, summed over devices
    op_ns: Dict[str, float]  # summed over devices
    kernels: List[OpEvent]  # Mosaic kernel calls of the first device
    gaps: List[Tuple[str, int]]  # (host span, idle ns) on the first device
    spans: List[Tuple[str, int, int]]  # the benchmark's host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def module_s(self, program: str) -> float:
        """Device seconds of the program named ``program`` (every hash of
        it), summed over chips."""
        return sum(v for k, v in self.module_ns.items()
                   if base_name(k) == program) / 1e9

    def top_ops(self, n: int) -> List[list]:
        top = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def top_gaps(self, n: int) -> List[list]:
        top = sorted(self.gaps, key=lambda g: -g[1])[:n]
        return [[k, v / 1e9] for k, v in top]


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {files}")
    return files[0]


def reduce_dir(trace_dir: str, span: Optional[str] = None) -> TraceSummary:
    return reduce_file(find_xplane(trace_dir), span)


def reduce_file(path: str, span: Optional[str] = None) -> TraceSummary:
    """Reduce one ``.xplane.pb``; the window is the host span ``span``
    (the whole device activity when None or absent)."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    spans: List[Tuple[str, int, int]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            devices.append((plane.name, lines))
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    if not devices:
        raise ValueError(f"no {DEVICE_PREFIX}* plane in {path}")
    devices.sort(key=lambda d: int(d[0][len(DEVICE_PREFIX):]))
    win = [s for s in spans if s[0] == span]
    if win:
        lo, hi = win[0][1], win[0][2]
    else:
        ev = [(e.start_ns, e.start_ns + e.duration_ns)
              for _, lines in devices for e in lines.get(OP_LINE, [])]
        lo, hi = min(s for s, _ in ev), max(e for _, e in ev)
    module_ns: Dict[str, float] = collections.Counter()
    op_ns: Dict[str, float] = collections.Counter()
    busy_total = 0.0
    kernels: List[OpEvent] = []
    gaps: List[Tuple[str, int]] = []
    for i, (_, lines) in enumerate(devices):
        for e in lines.get(MODULE_LINE, []):
            if e.start_ns < hi and e.start_ns + e.duration_ns > lo:
                module_ns[e.name] += e.duration_ns
        iv = []
        for e in lines.get(OP_LINE, []):
            if e.start_ns < hi and e.start_ns + e.duration_ns > lo:
                text = e.name
                op_ns[op_name(text)] += e.duration_ns
                iv.append((e.start_ns, e.start_ns + e.duration_ns))
                if i == 0 and KERNEL_TARGET in text:
                    kernels.append(OpEvent(text, e.duration_ns))
        busy = union(iv, lo, hi)
        busy_total += sum(e - s for s, e in busy)
        if i == 0:
            inner = [s for s in spans if s[0] != span]
            for s, e in gaps_of(busy, lo, hi):
                mid = (s + e) // 2
                host = [sp for sp in inner if sp[1] <= mid < sp[2]]
                label = min(host, key=lambda sp: sp[2] - sp[1])[0] \
                    if host else "host"
                gaps.append((label, e - s))
    return TraceSummary(window=(lo, hi), n_devices=len(devices),
                        busy_ns=busy_total / len(devices),
                        module_ns=dict(module_ns), op_ns=dict(op_ns),
                        kernels=kernels, gaps=gaps, spans=spans)
