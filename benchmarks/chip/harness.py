"""The pieces of one benchmark run: the cell's files, the program's job,
the timed window and the check of the job's answers against the plain
reference.

A *job* is what an FL researcher runs: one ``repro.core.run`` call of the
cell's strategy over the cell's clients, from a fresh start, for the
cell's ``T`` global iterations with evals every ``eval_every``.  Every job
of a run is the same job: the clients are rebuilt from the same arrays,
offsets and stream seed, so the window compiles nothing and each job's
answers are the same.
"""
from __future__ import annotations

import dataclasses
import json
import math
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import numpy as np

import reference
import traffic as traffic_lib

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


# ---------------------------------------------------------------------------
# Cells
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # configs/<config>.json
    traffic: dict  # traffic/<traffic>.json
    spec: dict  # cells/<cell>.json: T, eval_every, the check's limits


def _read(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, config: Optional[str] = None,
              traffic: Optional[str] = None, chips: int = 1,
              root: Path = HERE) -> Cell:
    """The cell ``name`` as ``BENCHMARK.json`` lists it; ``config`` and
    ``traffic`` name the files of a cell it does not list, under ``root``'s
    ``configs/``, ``traffic/`` and ``cells/``."""
    if config is None:
        bench = _read(ROOT / "BENCHMARK.json")
        entry = {w["name"]: w for w in bench["workloads"]}[name]
        config, traffic, chips = (entry["config"], entry["traffic"],
                                  entry["chips"])
    return Cell(name=name, chips=chips,
                config=_read(root / "configs" / f"{config}.json"),
                traffic=_read(root / "traffic" / f"{traffic}.json"),
                spec=_read(root / "cells" / f"{name}.json"))


@dataclasses.dataclass(frozen=True)
class Seeds:
    """Every random draw of a run, from ``--seed``."""

    seed: int

    @property
    def data(self) -> int:
        return self.seed

    @property
    def offsets(self) -> int:
        return self.seed + 1

    @property
    def streams(self) -> int:
        return self.seed + 2

    @property
    def run(self) -> int:  # w0 and the event stream: a 31-bit seed
        return self.seed % (2 ** 31 - 1)


def make_traffic(cell: Cell, seeds: Seeds):
    """(client datasets, network offsets) of the cell at ``seeds``."""
    K = cell.traffic["clients"]
    if cell.traffic["scenario"] != "always_on":
        raise ValueError(f"unknown scenario {cell.traffic['scenario']!r}")
    fam = reference.family(cell.config["model"])
    datasets = traffic_lib.make_datasets(cell.config["dataset"], K,
                                         seeds.data,
                                         getattr(fam, "GENERATORS", None))
    offsets = traffic_lib.network_offsets(K, seeds.offsets,
                                          cell.traffic["delay_range"])
    return datasets, offsets


# ---------------------------------------------------------------------------
# Compile accounting
# ---------------------------------------------------------------------------


class CompileClock:
    """Seconds and events of JAX tracing, lowering and compiling
    (persistent-cache reads included), and persistent-cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.events = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs
            self.events += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ---------------------------------------------------------------------------
# The program's job
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class JobOut:
    history: List[tuple]  # (global iteration, sim time, metrics) per eval
    preds: List[np.ndarray]  # eval predictions, in history order
    ticks: List[tuple]  # (global iteration after tick, folds, train loss)
    stats: dict
    finite: bool


class Program:
    """The system under test, set up once for a cell and a seed."""

    def __init__(self, cell: Cell, datasets, offsets, seeds: Seeds):
        from repro.configs import get_arch
        from repro.core import RunConfig, make_sim_clients, run
        from repro.models import LOCAL, build_model
        from repro.sim.profiles import DeviceProfile
        from repro.sim.telemetry import TelemetryLog
        from repro.sim.workloads import WORKLOADS, Workload

        self._run, self._make_clients = run, make_sim_clients
        self._telemetry = TelemetryLog
        m, tr, sp = cell.config["model"], cell.traffic, cell.spec
        fam = reference.family(m)
        self.cfg_model = dataclasses.replace(get_arch(m["arch"]),
                                             **fam.program_overrides(m))
        self.model = build_model(self.cfg_model, LOCAL)
        self.datasets = datasets
        self.profiles = [DeviceProfile(base_delay=o,
                                       compute_rate=tr["compute_rate"],
                                       jitter=tuple(tr["jitter"]))
                         for o in offsets]
        self.seeds = seeds
        self.cell = cell
        self.strategy = tr["strategy"]
        # the eval metric is the benchmark's: a workload of its own, which
        # also hands each eval's predictions to the check
        self._captured: List[np.ndarray] = []
        report = fam.report(m["task"])

        def capture(preds, targets):
            self._captured.append(np.array(preds, np.float32))
            return report(preds, targets)

        wl_name = f"chipbench.{cell.name}.{id(self)}"
        cm = self.cfg_model
        WORKLOADS.register(wl_name)(lambda: Workload(
            name=wl_name, task=m["task"], arch=m["arch"],
            in_features=cm.in_features, out_features=cm.out_features,
            hidden=cm.hidden, data_fn=None, eval_report=capture,
            headline=fam.headline(m["task"])))
        # the traffic's run_config goes to the program as it is
        self.cfg = RunConfig(**tr["run_config"], T=sp["T"],
                             eval_every=sp["eval_every"], seed=seeds.run,
                             task=m["task"], workload=wl_name)

    def job(self) -> JobOut:
        tr = self.cell.traffic
        self._captured = []
        with jax.profiler.TraceAnnotation("bench.job_setup"):
            clients = self._make_clients(
                self.datasets, profiles=self.profiles,
                seed=self.seeds.streams, start_frac=tr["start_frac"],
                growth=tr["growth"])
            stats, tel = {}, self._telemetry()
        with jax.profiler.TraceAnnotation("bench.run"):
            hist = self._run(self.strategy, self.model, self.cfg_model,
                             clients, self.cfg, stats=stats, telemetry=tel)
        with jax.profiler.TraceAnnotation("bench.extract"):
            history = [(h.global_iter, h.sim_time, h.metrics) for h in hist]
            finite = bool(history) and all(
                math.isfinite(v) for _, _, mt in history for v in mt.values())
            ticks = [(r.t, r.n_folds, r.values.get("train_loss"))
                     for r in tel.records]
        return JobOut(history=history, preds=self._captured, ticks=ticks,
                      stats=stats, finite=finite)


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Window:
    outs: List[Optional[JobOut]]  # None: the job raised
    seconds: float
    iters: int
    compile_events: int
    compile_s: float


def run_window(program: Program, seconds: float,
               clock: CompileClock) -> Window:
    """Jobs back to back until ``seconds`` have passed, ending with the
    first job that finishes after that."""
    e0, c0 = clock.events, clock.seconds
    outs: List[Optional[JobOut]] = []
    iters = 0
    t0 = time.perf_counter()
    while True:
        try:
            out = program.job()
            iters += int(out.stats["iters"])
        except Exception as e:  # noqa: BLE001 — a failed job is counted
            print(f"job raised: {e!r}", flush=True)
            out = None
        outs.append(out)
        if time.perf_counter() - t0 >= seconds:
            break
    return Window(outs=outs, seconds=time.perf_counter() - t0, iters=iters,
                  compile_events=clock.events - e0,
                  compile_s=clock.seconds - c0)


# ---------------------------------------------------------------------------
# The check
# ---------------------------------------------------------------------------

def replay_for(cell: Cell, out: JobOut, datasets, offsets, seeds: Seeds,
               dtype=None, fault: Optional[str] = None) -> reference.Replay:
    """The reference's replay of ``out``'s job up to its first eval."""
    import jax.numpy as jnp

    # the reference reads the traffic's knobs and its run_config as one
    run = dict(cell.traffic, **cell.traffic["run_config"])
    return reference.replay(
        cell.config["model"], run, datasets, offsets,
        run_seed=seeds.run, stream_seed=seeds.streams,
        stop=int(out.history[0][0]), dtype=dtype or jnp.float32,
        fault=fault)


N_EARLY_TICKS = 3


def compare(out: JobOut, ref: reference.Replay) -> Dict[str, float]:
    """The numbers the check can compare, for the job's first eval:

    * ``sched_mismatch``: ticks up to the eval whose (global iteration,
      folds) differ from the reference's, plus 1 if the eval's simulated
      time differs (an exact comparison of the event stream);
    * ``loss_gap``: the largest relative gap of a tick's cohort-mean train
      loss over the first ``N_EARLY_TICKS`` ticks (local rounds from the
      initial model, before training has amplified anything);
    * ``loss_gap_all``: the same over every tick up to the eval;
    * ``pred_gap``: the widest gap of an eval prediction, over the RMS of
      the reference's predictions.

    A cell's file names the ones it compares, each with its limit.
    """
    t_eval, sim_time, _ = out.history[0]
    prog = [(t, n) for t, n, _ in out.ticks if t <= t_eval]
    mism = sum(a != b for a, b in zip(prog, ref.ticks))
    mism += abs(len(prog) - len(ref.ticks)) + int(sim_time != ref.sim_time)
    losses = [l for t, _, l in out.ticks if t <= t_eval]
    gaps = [abs(p - r) / max(abs(r), 1e-12)
            for p, r in zip(losses, ref.tick_loss)]
    p = np.asarray(out.preds[0], np.float64)
    r = np.asarray(ref.preds, np.float64)
    if p.shape != r.shape:
        pred_gap = math.inf
    else:
        pred_gap = float(np.max(np.abs(p - r))
                         / max(np.sqrt(np.mean(r * r)), 1e-12))
    nums = {"sched_mismatch": float(mism),
            "loss_gap": max(gaps[:N_EARLY_TICKS], default=math.inf),
            "loss_gap_all": max(gaps, default=math.inf),
            "pred_gap": pred_gap}
    return {k: (float(v) if math.isfinite(v) else math.inf)
            for k, v in nums.items()}


def judge(nums: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(nums[k] <= limits[k] for k in limits)
