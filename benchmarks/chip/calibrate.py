"""Readings the check's limits are set from, at a cell's own size.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1 2 ... \\
        [--control-seeds 3] [--out calib.jsonl]

For each seed: the traffic, one job of the program through the window's
own call, the float32 reference, and the compared numbers (the *lower*
readings).  On the first ``--control-seeds`` seeds also the control (the
reference in bfloat16 in the program's place) and each fault planted in
the reference put in the program's place (the *upper* readings).
``--config``/``--traffic`` name the files of a cell ``BENCHMARK.json``
does not list.  One JSON line per reading.  Not run by the benchmark's
own runs.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    import harness
    import reference
    import run

    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: JAX finds no TPU", file=sys.stderr)
        return 2
    run.enable_cache()
    cell = harness.load_cell(args.workload, args.config, args.traffic)
    out_f = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out_f:
            out_f.write(line + "\n")
            out_f.flush()

    for i, seed in enumerate(args.seeds):
        seeds = harness.Seeds(seed)
        t0 = time.perf_counter()
        data, offsets = harness.make_traffic(cell, seeds)
        t_traffic = time.perf_counter() - t0
        program = harness.Program(cell, data, offsets, seeds)
        t0 = time.perf_counter()
        out = program.job()
        t_job = time.perf_counter() - t0
        del program
        t0 = time.perf_counter()
        ref = harness.replay_for(cell, out, data, offsets, seeds)
        t_ref = time.perf_counter() - t0
        emit({"cell": cell.name, "seed": seed, "side": "program",
              "nums": harness.compare(out, ref), "t_eval": out.history[0][0],
              "ticks": len(out.ticks), "iters": out.stats["iters"],
              "traffic_s": t_traffic, "job_s": t_job, "reference_s": t_ref})
        if i >= args.control_seeds:
            continue
        for fault, dtype in [(None, jnp.bfloat16)] + [
                (f, None) for f in reference.FAULTS]:
            t0 = time.perf_counter()
            alt = harness.replay_for(cell, out, data, offsets, seeds,
                                     dtype=dtype, fault=fault)
            stand_in = harness.JobOut(
                history=out.history, preds=[alt.preds],
                ticks=[(t, n, l) for (t, n), l in zip(
                    alt.ticks, alt.tick_loss)],
                stats=out.stats, finite=True)
            emit({"cell": cell.name, "seed": seed,
                  "side": fault or "control_bf16",
                  "nums": harness.compare(stand_in, ref),
                  "seconds": time.perf_counter() - t0})
    return 0


if __name__ == "__main__":
    sys.exit(main())
