"""One run of one cell of the chip benchmark.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Set-up (imports, traffic from the seed, one warm-up job that compiles or
loads every program the window runs) is ``setup_s``.  ``--trace 0`` then
runs jobs back to back for ``--seconds`` and reports ``iters_per_s``;
``--trace 1`` instead traces one job with the JAX profiler and reports
the per-layer metrics, read by ``metrics/<name>.py``.  Either way the
checked job's first eval is compared with the plain reference
(``reference.py``) once the timed part is over.  The last line of standard
output is one JSON object; without a TPU, or with fewer chips than the
cell asks for, the run exits non-zero and prints no result.

A new configuration enters as new files only; no file here is edited:

* ``configs/<config>.json``: ``model`` (the program's ``arch``, the
  ``family``, the ``task`` and the sizes the family reads) and
  ``dataset`` (a ``generator`` name and its keyword arguments);
* ``families/<family>.py``, once per family, importing nothing of
  ``repro``; ``reference.family`` loads it by file.  It gives
  ``param_shapes(model)`` (name -> (shape, ``"fan_in"`` or ``"zeros"``),
  the seeded init in ``reference.init_params`` reads it),
  ``first_layer(model)`` (the parameter ASO-Fed's feature pass
  reweights), ``forward(model, p, x)`` (outputs per row),
  ``loss(model, p, x, y)`` (the mean training loss, a scalar),
  ``train_flops_per_iter(config, traffic)`` (model FLOPs of one local
  round, for ``step_mfu_pct``), ``program_overrides(model)`` (the
  ``ModelConfig`` fields set on the program's ``arch``), ``report(task)``
  (a function of (predictions, targets) to a dict of eval metrics),
  ``headline(task)`` (the key of that dict the program leads with),
  ``pred_block(model)`` (test rows the reference predicts a call), and
  may give ``GENERATORS`` (name -> generator, beside ``traffic.py``'s);
* a generator: ``generator(n_clients=, seed=, **dataset)`` returning
  ``[(x_train, y_train, x_test, y_test)]`` per client, in the family
  module's ``GENERATORS``;
* ``traffic/<traffic>.json``: the strategy, clients, network and data
  stream knobs, and the ``run_config`` handed to the program as it is
  (``max_cohort`` and ``state_residency`` bind the reference too);
* ``cells/<cell>.json``: ``T``, ``eval_every`` and the check's limits;
* the entries in ``BENCHMARK.json``, and ``metrics/<name>.py`` for a new
  per-layer metric.

The check's comparison (``harness.compare``) is the same for every
family: the ticks, each tick's train loss and the eval's prediction
array, whatever its shape.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))


def _err(msg: str) -> None:
    print(f"run.py: {msg}", file=sys.stderr, flush=True)


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), holding every program,
    however fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def trace_options():
    """Device and host activity, without the Python function tracer (it
    multiplies the trace's size and slows the host it measures) and
    without the programs' HLO."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def per_layer_metrics(cell_name: str):
    """``BENCHMARK.json``'s per-layer metrics that this cell reports."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", [cell_name])]


def read_metric(name: str, ctx):
    """Run ``metrics/<name>.py``'s ``read(ctx)``; None when it finds
    nothing to read."""
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    import harness

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _err(f"JAX finds no TPU (platform {devices[0].platform!r})")
        return 2
    if len(devices) < cell.chips:
        _err(f"the cell needs {cell.chips} chips, JAX finds {len(devices)}")
        return 2
    return run_cell(cell, args, devices)


def run_cell(cell, args, devices) -> int:
    import jax

    import harness
    import peaks
    import trace_reduce

    cache_dir = enable_cache()
    clock = harness.CompileClock()
    seeds = harness.Seeds(args.seed)
    t_imports = time.perf_counter() - T_START
    import repro.core  # noqa: F401 — the program's import cost is set-up

    t0 = time.perf_counter()
    datasets, offsets = harness.make_traffic(cell, seeds)
    t_traffic = time.perf_counter() - t0
    program = harness.Program(cell, datasets, offsets, seeds)
    t0 = time.perf_counter()
    warm = program.job()
    t_warm = time.perf_counter() - t0
    setup_s = time.perf_counter() - T_START
    print(json.dumps({"setup": {
        "setup_s": setup_s, "imports_s": t_imports, "traffic_s": t_traffic,
        "warmup_job_s": t_warm, "compile_s": clock.seconds,
        "compile_events": clock.events, "cache_hits": clock.cache_hits,
        "compile_cache": cache_dir, "warmup_iters": warm.stats["iters"]}}),
        flush=True)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    metrics = {}
    breakdown = None
    if args.trace:
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        try:
            e0 = clock.events
            with jax.profiler.trace(tdir, profiler_options=trace_options()):
                with jax.profiler.TraceAnnotation("bench.job"):
                    t0 = time.perf_counter()
                    try:
                        out = program.job()
                    except Exception as e:  # noqa: BLE001
                        _err(f"traced job raised: {e!r}")
                        out = None
                    wall = time.perf_counter() - t0
            outs = [out]
            attempted = cell.spec["T"]
            summary = trace_reduce.reduce_dir(tdir, span="bench.job")
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        print(json.dumps({"window": {"traced_job_s": wall,
                                     "compile_events": clock.events - e0}}),
              flush=True)
        if out is not None:
            ctx = types.SimpleNamespace(
                trace=summary, stats=out.stats, ticks=out.ticks,
                iters=int(out.stats["iters"]), wall_s=wall, cell=cell,
                chips=cell.chips, peak=peaks.peak(dev.device_kind))
            for m in per_layer_metrics(cell.name):
                v = read_metric(m["name"], ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = {"device_ops": summary.top_ops(10),
                     "idle_gaps": summary.top_gaps(10)}
    else:
        win = harness.run_window(program, args.seconds, clock)
        outs = win.outs
        attempted = cell.spec["T"] * len(outs)
        print(json.dumps({"window": {
            "jobs": len(outs), "seconds": win.seconds, "iters": win.iters,
            "compile_events": win.compile_events,
            "compile_s": win.compile_s}}), flush=True)
        metrics["iters_per_s"] = {"value": win.iters / win.seconds,
                                  "unit": "iters/s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    failed = sum(cell.spec["T"] for o in outs if o is None or not o.finite)
    stats = dev.memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    # the check: one job drawn from the seed, its first eval against the
    # reference, run after the timed part with the program's state freed
    checked = outs[args.seed % len(outs)]
    limits = cell.spec["limits"]
    if checked is None or not checked.finite or not checked.history:
        nums = {k: float("inf") for k in limits}
    else:
        del program, warm
        ref = harness.replay_for(cell, checked, datasets, offsets, seeds)
        nums = harness.compare(checked, ref)
    correct = harness.judge(nums, limits) and failed == 0
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
