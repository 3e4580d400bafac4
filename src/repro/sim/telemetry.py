"""In-scan telemetry for the cohort engine: per-tick curves at any window.

The PR-4 megastep fused ``RunConfig.window`` ticks into one dispatch —
and with it, coarsened every host-visible signal to window boundaries
(the ROADMAP "windowed eval extraction" item).  This module restores
per-tick resolution without giving the fusion back:

* each dispatched tick emits one **telemetry row** — the masked cohort
  means of the per-client scalars the strategy's ``local`` computes
  anyway (train loss, step multipliers, ...; see
  ``Strategy.telemetry_slots``) — stacked by the megastep's ``lax.scan``
  into a ``[T_w, n_slots]`` block that rides the *same* dispatch as the
  window itself: zero extra dispatches, zero extra transfers, zero syncs;
* the builder records per-tick **host metadata** (fold counts, staleness
  sums, arrival times: ``repro.sim.prefetch.TickMeta``) on the producer
  thread, for free;
* :class:`TelemetryLog` joins the two — device blocks are kept un-read
  until :meth:`finalize` (end of run, same policy as the engine's
  deferred eval extraction), then materialized once into
  :class:`TickRecord` rows.

The host path's own timing lives here too: :func:`span` puts one
``repro.*`` interval (a run phase, a window's build, wait or dispatch, an
eval) on the profiler's clock and feeds the same interval to the
engine's phase-seconds stats, so the trace and the stats read one clock.

Because a tick always executes at its unfused shape bucket, its telemetry
row is **bit-identical across window sizes** for the fp32 codec — the
``window=32`` loss curve is the ``window=1`` loss curve, point for point
(pinned by ``tests/test_telemetry.py``).  For *eval* metrics (which need
a host-side predict over the test splits) the engine offers
``RunConfig.eval_align``: windows are split at ``eval_every`` fold
boundaries so evals land exactly where a ``window=1`` run would put them
— a dispatch-count trade the caller opts into, never a numerics change.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import TYPE_CHECKING, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

if TYPE_CHECKING:  # prefetch imports this module's spans
    from repro.sim.prefetch import PreparedTick

Array = np.ndarray


# ---------------------------------------------------------------------------
# Program spans on the profiler's clock
# ---------------------------------------------------------------------------


class span:
    """A context manager that opens ``jax.profiler.TraceAnnotation(name,
    **args)`` (a host event on the profiler's clock, on the calling
    thread, recorded only while a trace is running) and, given an
    accumulator, adds the same interval's ``perf_counter`` seconds to
    ``owner.<attr>``.

    The engine's phase seconds (``host_build_s``, ``device_s``,
    ``eval_s``, ``gather_s``, ``scatter_s``, ...) are sums of their
    spans, so a stats key and the trace time the same interval.  A span
    costs about a microsecond with the profiler off: open one per window,
    eval or run phase, never per arrival or per row."""

    __slots__ = ("_annotation", "_owner", "_attr", "_t0")

    def __init__(self, name: str, owner=None, attr: Optional[str] = None,
                 **args):
        self._annotation = jax.profiler.TraceAnnotation(name, **args)
        self._owner, self._attr = owner, attr
        self._t0 = 0.0

    def __enter__(self) -> "span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._owner is not None:
            setattr(self._owner, self._attr, getattr(self._owner, self._attr)
                    + time.perf_counter() - self._t0)
        self._annotation.__exit__(*exc)


class FullCollections:
    """While entered, every full (generation-2) garbage collection of the
    process is a ``repro.gc`` span on the thread that ran it, and
    ``count`` counts them; younger collections return at once."""

    def __init__(self):
        self.count = 0
        self._open: Optional[span] = None

    def _callback(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._open = span("repro.gc").__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
            self.count += 1

    def __enter__(self) -> "FullCollections":
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._callback)


@dataclasses.dataclass(frozen=True)
class TickRecord:
    """One scheduler tick's summary: in-scan slot values + host metadata."""

    t: int  # global iteration after this tick's folds
    sim_time: float
    n_folds: int  # arrivals folded (participation)
    staleness_mean: float
    staleness_max: int
    values: Dict[str, float]  # slot name -> masked cohort mean


class TelemetryLog:
    """Accumulates per-tick telemetry across a run's dispatches.

    ``append`` stores the device block *without reading it* — pulling a
    device array to host would serialize the tick pipeline, so blocks
    stay device-resident until :meth:`finalize` (the engine calls it
    after the dispatch loop; callers handing their own log to
    ``run_strategy`` receive it finalized).
    """

    def __init__(self, slots: Sequence[str] = ()):
        self.slots: Tuple[str, ...] = tuple(slots)
        self.records: List[TickRecord] = []
        self._pending: List[Tuple[Tuple, int, object]] = []

    def append(self, pt: PreparedTick, tel_block) -> None:
        # keep only the host metadata + the (tiny) device block: holding
        # the PreparedTick itself would pin every window's staging-block
        # device buffers (xs/ys/...) until finalize — O(windows) device
        # memory instead of the builder's O(NSLOTS) rotation
        self._pending.append((pt.ticks_meta, pt.n_ticks, tel_block))

    def finalize(self) -> List[TickRecord]:
        """Materialize pending device blocks into :class:`TickRecord` rows
        (one host read per dispatch, after the run)."""
        for ticks_meta, n_ticks, block in self._pending:
            arr = np.asarray(block, np.float32).reshape(-1, len(self.slots)) \
                if len(self.slots) else np.zeros((n_ticks, 0), np.float32)
            for j, tm in enumerate(ticks_meta):
                vals = {s: float(arr[j, k])
                        for k, s in enumerate(self.slots)}
                self.records.append(TickRecord(
                    t=tm.t_end, sim_time=tm.sim_time, n_folds=tm.n_folds,
                    staleness_mean=(tm.staleness_sum / tm.n_folds
                                    if tm.n_folds else 0.0),
                    staleness_max=tm.staleness_max, values=vals,
                ))
        self._pending.clear()
        return self.records

    # -- extraction ------------------------------------------------------
    def curve(self, slot: str) -> Tuple[Array, Array]:
        """(t, value) arrays for one slot — per-tick resolution regardless
        of the window size the run dispatched at."""
        if slot not in self.slots:
            raise KeyError(
                f"unknown telemetry slot {slot!r}; this run recorded "
                f"{list(self.slots)}")
        self.finalize()
        ts = np.array([r.t for r in self.records], np.int64)
        vs = np.array([r.values[slot] for r in self.records], np.float32)
        return ts, vs

    def loss_curve(self) -> Tuple[Array, Array]:
        """The per-tick train-loss curve (the ``"train_loss"`` slot)."""
        return self.curve("train_loss")

    def summary(self) -> Dict[str, float]:
        """Run-level reductions for the engine's ``stats`` dict."""
        self.finalize()
        out: Dict[str, float] = {}
        if not self.records:
            return out
        folds = sum(r.n_folds for r in self.records)
        out["participation_mean"] = folds / len(self.records)
        for s in self.slots:
            # fold-weighted mean over ticks + the final tick's value
            tot = sum(r.values[s] * r.n_folds for r in self.records)
            out[f"{s}_mean"] = tot / max(folds, 1)
            out[f"{s}_final"] = self.records[-1].values[s]
        return out


def eval_cut_positions(fold_counts: Sequence[int], t_start: int,
                       eval_every: int) -> List[int]:
    """Indices *after which* a window's tick list must be split so eval
    points land exactly where a ``window=1`` run would put them.

    ``window=1`` evaluates after the first tick whose fold count crosses
    a multiple of ``eval_every``; splitting the fused window at those
    ticks reproduces that cadence without changing any tick's shape
    bucket (so the split is bitwise-free for the fp32 codec).
    """
    cuts: List[int] = []
    next_cut = (t_start // eval_every + 1) * eval_every
    run_t = t_start
    for j, n in enumerate(fold_counts):
        run_t += n
        if run_t >= next_cut:
            if j + 1 < len(fold_counts):
                cuts.append(j + 1)
            while next_cut <= run_t:
                next_cut += eval_every
    return cuts


def split_at_evals(ticks: List[List], t_start: int, eval_every: int,
                   count=len) -> List[List[List]]:
    """Split a window's tick list into eval-aligned segments.

    ``count`` maps one tick to the folds it will charge (the engine
    passes its trainable-arrival counter).  Segment boundaries become
    dispatch boundaries, which is where the engine's consuming loop
    checks the eval cadence.
    """
    cuts = eval_cut_positions([count(tk) for tk in ticks], t_start,
                              eval_every)
    segs: List[List[List]] = []
    prev = 0
    for c in cuts + [len(ticks)]:
        if c > prev:
            segs.append(ticks[prev:c])
        prev = c
    return segs
