"""Benchmark harness: one entry per paper table/figure + roofline + kernels.

Prints ``name,us_per_call,derived`` CSV (one line per benchmark) and writes
full JSON artifacts under results/paper/.

    PYTHONPATH=src python -m benchmarks.run            # default (quick)
    PYTHONPATH=src python -m benchmarks.run --full     # paper-scale budgets
    PYTHONPATH=src python -m benchmarks.run --only table5
    PYTHONPATH=src python -m benchmarks.run --smoke    # cohort-engine sweep
                                                       # -> BENCH_sim.json
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def _fmt(name, us, derived):
    return f"{name},{us:.1f},{derived}"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="substring filter: "
                         "table5|fig4|fig5|roofline|kernel|sim")
    ap.add_argument("--recompute", action="store_true",
                    help="ignore cached results/paper artifacts")
    ap.add_argument("--smoke", action="store_true",
                    help="cohort-engine clients-vs-throughput sweep at "
                         "{8, 64, 256} clients; writes BENCH_sim.json")
    ap.add_argument("--devices", type=int, default=1,
                    help="with --smoke: force N virtual host devices to "
                         "exercise the sharded cohort path on CPU")
    ap.add_argument("--scenario", default=None,
                    help="with --smoke: also run the pipelined engine under "
                         "an availability-trace scenario (diurnal|bursty|"
                         "churn|flash|trace:<path>); churn records land in "
                         "BENCH_sim.json next to the always-on sweep")
    ap.add_argument("--window", type=int, default=32,
                    help="with --smoke: async ticks fused per megastep "
                         "dispatch in the cohort modes (1 = per-tick)")
    ap.add_argument("--state-dtype", default=None,
                    help="with --smoke: stacked client-state storage dtype "
                         "(fp32 = full-copy master, bf16 = delta-"
                         "compressed); unknown names fail fast with the "
                         "accepted list")
    ap.add_argument("--state-residency", default="device",
                    help="with --smoke: where the stacked client state "
                         "lives in the sweep modes (device = resident; "
                         "host = out-of-core pool with per-window "
                         "active-cohort gather/scatter); unknown names "
                         "fail fast")
    ap.add_argument("--ksweep-counts", default="10000,100000",
                    help="with --smoke: comma-separated registered-fleet "
                         "sizes for the K-sweep memory records "
                         "(kind=k_sweep; 'none' or '' disables)")
    ap.add_argument("--ksweep-cohort", type=int, default=64,
                    help="with --smoke: active (non-stub) clients in each "
                         "K-sweep run — device memory under host "
                         "residency is bounded by this, not K")
    ap.add_argument("--workload", default="lstm_regression",
                    help="with --smoke: registered repro.sim.workloads "
                         "name the sweep runs (validated against the "
                         "registry before the sweep; every registered "
                         "workload additionally gets one small-cohort "
                         "smoke record unless --no-workload-smoke)")
    ap.add_argument("--no-workload-smoke", action="store_true",
                    help="with --smoke: skip the per-registered-workload "
                         "small-cohort records")
    ap.add_argument("--mem-cohort", type=int, default=1024,
                    help="with --smoke: cohort size for the fp32-vs-bf16 "
                         "stacked-state memory pair (0 disables)")
    ap.add_argument("--fold-mode", default="sequential",
                    help="with --smoke: server-fold evaluation order of "
                         "the engine modes (sequential|associative|auto; "
                         "non-sequential sweeps drop asofed's non-affine "
                         "feature pass)")
    ap.add_argument("--fold-cohorts", default="256,1024",
                    help="with --smoke: comma-separated cohort sizes for "
                         "the sequential-vs-associative fold pair "
                         "('none' or '' disables)")
    ap.add_argument("--upload-codec", default="identity",
                    help="with --smoke: client->server upload codec the "
                         "sweep runs under (identity|topk_sparse|"
                         "random_mask|quantized_delta); validated before "
                         "the sweep")
    ap.add_argument("--frontier-cohort", type=int, default=16,
                    help="with --smoke: cohort size for the per-codec "
                         "accuracy-vs-bytes upload frontier records "
                         "(0 disables)")
    ap.add_argument("--faults", default=None,
                    help="with --smoke: comma-separated per-upload fault "
                         "rates (e.g. '0.0,0.1'); each adds one fault-"
                         "injected cohort record (kind=fault_matrix) "
                         "carrying the chaos counters (lost/retried/"
                         "crashed/duplicated/corrupted/rejected/clipped) "
                         "and the degraded final metric")
    args = ap.parse_args()
    quick = not args.full
    want = lambda s: args.only is None or args.only in s  # noqa: E731

    if args.smoke and args.devices > 1 and "jax" not in sys.modules:
        # partition the host into virtual devices so the engine's
        # data-mesh shard_map path can be benchmarked on CPU.  Must be set
        # before the first jax import; appended so an operator's existing
        # XLA_FLAGS (and any device count they forced there) still apply.
        flag = f"--xla_force_host_platform_device_count={args.devices}"
        existing = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in existing:
            os.environ["XLA_FLAGS"] = f"{existing} {flag}".strip()

    from repro.common.compile_cache import enable_compile_cache

    enable_compile_cache()
    rows = []
    print("name,us_per_call,derived")

    if args.smoke or (args.only and want("sim")):
        from benchmarks.sim_bench import bench_sim

        fold_cohorts = (tuple(int(k) for k in args.fold_cohorts.split(","))
                        if args.fold_cohorts not in ("", "none") else ())
        fault_rates = (tuple(float(x) for x in args.faults.split(","))
                       if args.faults else ())
        ksweep_counts = (tuple(int(k) for k in args.ksweep_counts.split(","))
                         if args.ksweep_counts not in ("", "none") else ())
        for r in bench_sim(scenario=args.scenario, window=args.window,
                           state_dtype=args.state_dtype,
                           mem_cohort=args.mem_cohort,
                           workload=args.workload,
                           workload_smoke=not args.no_workload_smoke,
                           fold_mode=args.fold_mode,
                           fold_cohorts=fold_cohorts,
                           upload_codec=args.upload_codec,
                           frontier_cohort=args.frontier_cohort,
                           fault_rates=fault_rates,
                           state_residency=args.state_residency,
                           ksweep_counts=ksweep_counts,
                           ksweep_cohort=args.ksweep_cohort):
            rows.append(r)
            print(_fmt(*r), flush=True)
        if args.smoke:  # smoke mode runs only the sim sweep
            return

    if want("kernel"):
        from benchmarks.kernel_bench import bench

        for r in bench():
            rows.append(r)
            print(_fmt(*r), flush=True)

    if want("roofline"):
        from benchmarks.roofline_table import csv_rows, load_results

        for mesh in ("pod1", "pod2"):
            for r in csv_rows(load_results(mesh)):
                rows.append(r)
                print(_fmt(*r), flush=True)

    table51_results = None
    if want("table5") or want("table6") or want("fig6"):
        import json
        import os

        from benchmarks.paper_tables import (OUT_DIR, fig_6_growth, table_5_1,
                                             table_6_1)

        t0 = time.perf_counter()
        cache = os.path.join(OUT_DIR, "table_5_1.json")
        if not args.recompute and os.path.exists(cache):
            # federated runs checkpoint incrementally (a full recompute is
            # ~1 h on one CPU core); reuse the measured artifacts
            with open(cache) as f:
                table51_results = json.load(f)["results"]
            print("# table5: summarizing cached results/paper/table_5_1.json "
                  "(pass --recompute to rerun)", flush=True)
        else:
            table51_results = table_5_1(quick=quick)
        wall = (time.perf_counter() - t0) * 1e6
        # headline: ASO-Fed vs FedAvg on each dataset (paper improv.(1))
        for ds, per in table51_results.items():
            if "asofed" not in per or "fedavg" not in per:
                continue  # dataset only partially benchmarked
            key = "smape" if "smape" in per["asofed"] else "accuracy"
            a = per["asofed"].get(key)
            f = per["fedavg"].get(key)
            if a is None or f is None:
                continue
            if key == "smape":
                improv = (f - a) / f * 100 if f else 0.0
            else:
                improv = (a - f) / f * 100 if f else 0.0
            r = (f"paper/table5.1/{ds}", wall / len(table51_results),
                 f"asofed_{key}={a:.4f};fedavg_{key}={f:.4f};improv={improv:+.1f}%")
            rows.append(r)
            print(_fmt(*r), flush=True)
        t61 = table_6_1(table51_results)
        for ds, per in t61.items():
            if "asofed" not in per or "fedavg" not in per:
                continue
            a_it = per["asofed"].get("iters") or 0
            f_it = per["fedavg"].get("iters") or 0
            if not a_it or not f_it:
                continue  # partially benchmarked dataset
            r = (f"paper/table6.1/{ds}", 0.0,
                 f"iters_per_budget_asofed={a_it};fedavg={f_it};"
                 f"speedup={a_it/max(f_it,1):.1f}x")
            rows.append(r)
            print(_fmt(*r), flush=True)
        fig_6_growth(table51_results)

    if want("fig4"):
        import json
        import os

        from benchmarks.paper_tables import OUT_DIR, fig_4_dropouts

        t0 = time.perf_counter()
        cache4 = os.path.join(OUT_DIR, "fig_4_dropout.json")
        if not args.recompute and os.path.exists(cache4):
            with open(cache4) as f:
                f4 = json.load(f)
            print("# fig4: summarizing cached artifact", flush=True)
        else:
            f4 = fig_4_dropouts(quick=quick)
        wall = (time.perf_counter() - t0) * 1e6
        for ds, per_alg in f4.items():
            key = "smape" if ds == "airquality" else "f1"
            pts = {r_: m.get(key) for r_, m in per_alg["asofed"].items()
                   if m.get(key) is not None}
            if not pts:
                continue
            worst = max(pts.keys(), key=float)
            r = (f"paper/fig4/{ds}", wall / 2,
                 f"asofed_{key}@0%={pts.get('0.0', float('nan')):.4f};"
                 f"@{float(worst):.0%}={pts[worst]:.4f}")
            rows.append(r)
            print(_fmt(*r), flush=True)

    if want("fig5"):
        import json
        import os

        from benchmarks.paper_tables import OUT_DIR, fig_5_periodic

        t0 = time.perf_counter()
        cache5 = os.path.join(OUT_DIR, "fig_5_periodic.json")
        if not args.recompute and os.path.exists(cache5):
            with open(cache5) as f:
                f5 = json.load(f)
            print("# fig5: summarizing cached artifact", flush=True)
        else:
            f5 = fig_5_periodic(quick=quick)
        wall = (time.perf_counter() - t0) * 1e6
        key = "smape"
        vals = {k: v[-1][key] for k, v in f5.items() if v}
        r = ("paper/fig5/periodic_dropout", wall,
             ";".join(f"p{k}={v:.4f}" for k, v in sorted(vals.items())))
        rows.append(r)
        print(_fmt(*r), flush=True)

    if not rows:
        print("no benchmarks selected", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
