"""The program's own spans in a profiler trace, and the device idle gaps
they name.

The engine opens one ``jax.profiler.TraceAnnotation`` per run phase,
window and eval, each named ``repro.*`` (``repro.sim.telemetry.span``):
``repro.run`` around a whole run on the thread that dispatches, its
set-up, window waits, dispatches and evals inside it on that thread, and
the window builds on the prefetch thread.  Beside what ``trace_reduce``
reads (the benchmark's ``bench.*`` spans and the device), this reads

* ``spans``: every ``repro.*`` host event with the host line (thread) it
  is on;
* ``gaps``: the first chip's idle intervals inside each ``repro.run``,
  each labelled with the innermost ``repro.*`` span below ``repro.run``
  open at the gap's midpoint on the run's own line, or ``unattributed``
  when only ``repro.run`` is open there.

A trace of a program without such spans gives empty lists and None
readings.  As a script, on a TPU, it traces one job of a cell as
``run.py --trace 1`` does and prints what the spans show:

    python3 benchmarks/chip/program_trace.py --workload <cell> --seed <n>
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import trace_reduce  # noqa: E402

PREFIX = "repro."
RUN = "repro.run"
UNATTRIBUTED = "unattributed"

Span = Tuple[str, str, int, int]  # (name, host line, start ns, end ns)


@dataclasses.dataclass
class ProgramTrace:
    spans: List[Span]
    gaps: List[Tuple[str, int, int]]  # (label, start, end ns) in repro.run

    def lines_of(self, name: str) -> set:
        return {ln for n, ln, _, _ in self.spans if n == name}

    def top_gaps(self, n: int) -> List[list]:
        top = sorted(self.gaps, key=lambda g: g[1] - g[2])[:n]
        return [[k, (e - s) / 1e9] for k, s, e in top]

    def idle_by_span(self) -> Dict[str, float]:
        """Idle seconds inside ``repro.run`` by label, largest first."""
        by = collections.Counter()
        for label, s, e in self.gaps:
            by[label] += (e - s) / 1e9
        return dict(by.most_common())

    @property
    def idle_unattributed_pct(self) -> Optional[float]:
        """Share of the device idle time inside ``repro.run`` that no
        program span below it explains; None without such idle time."""
        idle = sum(e - s for _, s, e in self.gaps)
        if idle <= 0:
            return None
        lost = sum(e - s for label, s, e in self.gaps
                   if label == UNATTRIBUTED)
        return 100.0 * lost / idle

    def around(self, t0: int, t1: int) -> List[list]:
        """The spans that overlap [t0, t1], with their start and end in
        ms from ``t0``."""
        return [[n, ln, (s - t0) / 1e6, (e - t0) / 1e6]
                for n, ln, s, e in sorted(self.spans, key=lambda sp: sp[2])
                if s < t1 and e > t0 and n != RUN]


def read_file(path: str) -> ProgramTrace:
    """The program spans and labelled gaps of one ``.xplane.pb``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    spans: List[Span] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            ops = [ln for ln in plane.lines
                   if ln.name == trace_reduce.OP_LINE]
            devices.append((int(plane.name[len(trace_reduce.DEVICE_PREFIX):]),
                            ops))
            continue
        for i, ln in enumerate(plane.lines):
            line = f"{plane.name}:{i}:{ln.name}"
            for ev in ln.events:
                if ev.name.startswith(PREFIX):
                    spans.append((ev.name, line, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    gaps: List[Tuple[str, int, int]] = []
    runs = [sp for sp in spans if sp[0] == RUN]
    if devices and runs:
        _, ops = min(devices, key=lambda d: d[0])
        iv = [(e.start_ns, e.start_ns + e.duration_ns)
              for ln in ops for e in ln.events]
        for _, line, lo, hi in runs:
            inner = [sp for sp in spans
                     if sp[1] == line and sp[0] != RUN
                     and lo <= sp[2] and sp[3] <= hi]
            busy = trace_reduce.union(iv, lo, hi)
            for s, e in trace_reduce.gaps_of(busy, lo, hi):
                gaps.append((label_at((s + e) // 2, inner), s, e))
    return ProgramTrace(spans=spans, gaps=gaps)


def read_dir(trace_dir: str) -> ProgramTrace:
    return read_file(trace_reduce.find_xplane(trace_dir))


def label_at(t: int, spans: List[Span]) -> str:
    """The innermost of ``spans`` open at ``t``, or ``unattributed``."""
    open_ = [sp for sp in spans if sp[2] <= t < sp[3]]
    if not open_:
        return UNATTRIBUTED
    return min(open_, key=lambda sp: sp[3] - sp[2])[0]


def span_table(pt: ProgramTrace) -> Dict[str, dict]:
    """Per span name: count, summed seconds and the host lines."""
    out: Dict[str, dict] = {}
    for name, line, s, e in pt.spans:
        row = out.setdefault(name, {"n": 0, "s": 0.0, "lines": set()})
        row["n"] += 1
        row["s"] += (e - s) / 1e9
        row["lines"].add(line)
    return {k: {"n": v["n"], "s": v["s"], "lines": sorted(v["lines"])}
            for k, v in sorted(out.items())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    import jax

    import harness
    import run

    if jax.devices()[0].platform != "tpu":
        print("program_trace.py: JAX finds no TPU", file=sys.stderr)
        return 2
    run.enable_cache()
    cell = harness.load_cell(args.workload)
    seeds = harness.Seeds(args.seed)
    data, offsets = harness.make_traffic(cell, seeds)
    program = harness.Program(cell, data, offsets, seeds)
    program.job()
    tdir = tempfile.mkdtemp(prefix="chipbench-spans-")
    try:
        with jax.profiler.trace(tdir,
                                profiler_options=run.trace_options()):
            with jax.profiler.TraceAnnotation("bench.job"):
                t0 = time.perf_counter()
                out = program.job()
                wall = time.perf_counter() - t0
        summary = trace_reduce.reduce_dir(tdir, span="bench.job")
        pt = read_dir(tdir)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    iters = int(out.stats["iters"])
    print(json.dumps({"program_gaps": pt.top_gaps(10)}))
    print(json.dumps({"program_spans": span_table(pt)}))
    print(json.dumps({"spans": {
        "workload": cell.name, "seed": args.seed, "traced_job_s": wall,
        "iters": iters,
        "window_wait_us_per_iter": out.stats["window_wait_s"] / iters * 1e6,
        "run_setup_ms": out.stats["run_setup_s"] * 1e3,
        "idle_unattributed_pct": pt.idle_unattributed_pct,
        "idle_in_run_s": sum(e - s for _, s, e in pt.gaps) / 1e9,
        "idle_by_span": pt.idle_by_span(),
        "device_idle_pct": 100.0 * (1 - summary.busy_s / summary.window_s),
        "gc_full_collections": out.stats["gc_full_collections"],
        "stats": {k: out.stats[k] for k in (
            "host_build_s", "device_s", "eval_s", "window_wait_s",
            "run_setup_s", "windows", "ticks")},
        "top_ops": summary.top_ops(10)}}), flush=True)
    (run0,) = [sp[2] for sp in pt.spans if sp[0] == RUN]
    for label, s, e in sorted(pt.gaps, key=lambda g: g[1] - g[2])[:4]:
        print(json.dumps({"gap": [label, (s - run0) / 1e6, (e - s) / 1e6],
                          "around": pt.around(s, e)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
