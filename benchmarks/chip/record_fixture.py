"""Record the small trace ``test_chip_bench_trace.py`` reads: one job of a
small FedAsync CNN cell (8 clients, 32 global iterations; the associative
fold's ``linear_scan`` kernel runs at fold buckets of 8) traced as
``run.py --trace 1`` traces.  Run on a TPU:

    python3 benchmarks/chip/record_fixture.py
"""
from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

FIXTURE = HERE / "fixtures" / "cnn_fedasync_small.xplane.pb"


def main() -> int:
    import jax

    import harness
    import run
    from small_cells import small_cell

    if jax.devices()[0].platform != "tpu":
        print("record_fixture.py: JAX finds no TPU", file=sys.stderr)
        return 2
    cell = small_cell("cnn_fmnist.fedasync", "cnn_fmnist.fedasync.k256",
                      clients=8)
    cell.config["model"]["hidden"] = 32
    cell.spec.update(T=32, eval_every=16)
    seeds = harness.Seeds(1)
    data, offsets = harness.make_traffic(cell, seeds)
    program = harness.Program(cell, data, offsets, seeds)
    program.job()
    tdir = tempfile.mkdtemp(prefix="chipbench-fixture-")
    try:
        with jax.profiler.trace(tdir, profiler_options=run.trace_options()):
            with jax.profiler.TraceAnnotation("bench.job"):
                program.job()
        FIXTURE.parent.mkdir(exist_ok=True)
        shutil.copy(__import__("trace_reduce").find_xplane(tdir), FIXTURE)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    print(f"{FIXTURE}: {FIXTURE.stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
