"""Vectorized cohort executor for event-driven federated simulation.

The engine drains a scheduler in **ticks**.  A tick is a maximal run of
pending arrivals with pairwise-distinct clients (capped at ``max_cohort``):

1. every client arriving in the tick runs its local round in ONE
   ``jax.vmap``-ed jit call over the stacked per-client state pytree
   (leading client axis, scratch row for padded slots);
2. the server folds the cohort's uploads **in arrival order** with
   ``jax.lax.scan`` — the sequential recurrence of the paper's Eq. (4)
   and the Eq. (5)-(6) feature pass are preserved exactly (each client
   receives the central model as of its own fold, bit-for-bit the state
   it would have seen in a per-arrival loop, up to fp reassociation);
3. evaluation is one batched/padded predict over all clients instead of
   K separate device round-trips.

This module is the **orchestration layer** of a layered run-loop; the
other layers are separable and individually tested:

* tick *building* (staging buffers, prefetch thread, per-tick host
  metadata) — ``repro.sim.prefetch``;
* tick *compilation* (traceable tick body, fused megastep, compiled-fn
  caches) — ``repro.sim.compile``;
* *telemetry* (the scan-carried per-tick metric accumulator + log) —
  ``repro.sim.telemetry``;
* *evaluation* (batched predict + pluggable metric bundles) —
  ``repro.sim.evaluation``;
* *workloads* (model spec + loss + metrics + stream factory, registered
  by name) — ``repro.sim.workloads``.

The tick loop is **pipelined, device-resident, and windowed**: the async
engine fuses a *window* of ``RunConfig.window`` consecutive ticks into one
**megastep** — a single ``jit(lax.scan(tick))`` dispatch over a stacked
``[T_w, bucket, ...]`` staging block — eliminating T−1 of every T
dispatches, host→device transfers, and ``block_until_ready`` syncs.  Each
fused tick emits one in-scan telemetry row (masked cohort means of the
scalars the local rounds already compute), so per-tick train-loss /
staleness / participation curves keep full resolution at any window size
with zero extra dispatches; with ``RunConfig.eval_align`` windows are
additionally split at ``eval_every`` fold boundaries so host evals land
exactly where a ``window=1`` run would put them.  Host batch building
runs on a prefetch thread (``repro.sim.prefetch``) that fills
pre-allocated per-bucket staging buffers (speculating via
``AsyncScheduler.peek_window``/``commit``) and transfers them while the
previous window executes, the stacked client state lives on device between
windows (donated on accelerators), and on a multi-device ``data`` mesh the
client axis of the stacked state, the cohort inputs (window axis
replicated), and the batched eval are sharded with the
``repro.common.sharding`` cohort rules (single device degrades to the
plain path).  Evaluation metric extraction is deferred to the end of the
run so eval dispatches never serialize the tick loop.

Per-client-state strategies can additionally store the stacked state
**delta-compressed** (``RunConfig.state_dtype``): parameter-like slots are
kept as ``w_k − w0`` in a reduced dtype behind a
:class:`repro.core.algorithms.common.ClientStateCodec` and reconstructed
inside the vmapped local round, roughly halving stacked-state memory at
bf16.  The fp32 codec is the identity (bitwise master precision).

Because the scheduler draws every delay/skip at pop time, the arrival
stream is invariant to how it is chunked into ticks AND windows, and to
whether the next window is built speculatively: the engine at any
``max_cohort`` (including 1) and any ``window``, with prefetch on or off,
replays the same trajectory (bit-for-bit across window sizes for the fp32
codec) — the property the equivalence tests pin down.

Algorithms plug in as :class:`Strategy` objects (see
``repro.core.algorithms``) supplying only the local-update and
aggregation rules; all heap/dropout/eval/history plumbing lives here,
compiled once per (model, config) rather than once per runner.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import time
import types
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.common import dtypes as dtypes_lib
from repro.common import sharding as sharding_lib
from repro.sim import compile as compile_lib
from repro.sim.evaluation import Evaluator
from repro.sim.prefetch import TickBuilder, TickPrefetcher, bucket_size
from repro.sim.profiles import SimClient
from repro.sim.scheduler import AsyncScheduler, SyncScheduler, SweepScheduler
from repro.sim.streaming import OnlineStream
from repro.sim.telemetry import (FullCollections, TelemetryLog, span,
                                 split_at_evals)
from repro.sim.traces import utilization as availability_utilization
from repro.sim.workloads import resolve_eval_report

Array = np.ndarray


# ---------------------------------------------------------------------------
# Run configuration / history (public API, re-exported by repro.core)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunConfig:
    T: int = 200  # global iterations (async) / rounds (sync)
    sim_time_budget: Optional[float] = None  # stop on simulated seconds
    batch_size: int = 32
    local_epochs: int = 2  # E
    eta: float = 0.01  # eta_bar (paper used 0.001 with many more iters)
    lam: float = 1.0  # prox coefficient lambda
    beta: float = 0.001  # decay coefficient
    # the traceable loss selector ("regression" | "classification" |
    # "multilabel"); `workload`, when set, names a registered
    # repro.sim.workloads entry whose metric bundle replaces the
    # task-string default at eval time (the pair must agree)
    task: str = "regression"
    workload: Optional[str] = None
    eval_every: int = 10  # 0 disables evaluation entirely (bench runs)
    seed: int = 0
    # ablations / robustness knobs
    feature_learning: bool = True  # ASO-Fed(-F) when False
    dynamic_lr: bool = True  # ASO-Fed(-D) when False
    dropout_frac: float = 0.0  # Fig. 4: fraction permanently dropped
    periodic_dropout: float = 0.0  # Fig. 5: per-iteration skip probability
    # FedAvg / FedProx
    participation: float = 0.2  # C
    prox_mu: float = 0.0  # FedProx mu
    # FedAsync
    fedasync_alpha: float = 0.6
    fedasync_staleness_exp: float = 0.5
    # FedBuff (buffered async aggregation)
    buffer_size: int = 8  # M: staleness-weighted deltas per server flush
    fedbuff_lr: float = 1.0  # server step applied to the buffered mean
    # engine
    max_cohort: Optional[int] = None  # cap on clients per tick (None: all)
    # build ticks on a side thread (None: adaptive — on for accelerators
    # and >=4-core CPU hosts, off on smaller boxes where the builder
    # thread would steal cycles from XLA; bit-identical either way)
    prefetch: Optional[bool] = None
    # megastep: fuse `window` consecutive async ticks into one
    # jit(lax.scan) dispatch (1 = per-tick dispatch).  `eval_align` splits
    # windows at `eval_every` fold boundaries so evals land exactly where
    # a window=1 run would put them (full loss-curve resolution at the
    # price of extra dispatches; off = PR-4 behavior, evals on window
    # boundaries — per-tick *train*-loss telemetry is free either way).
    # `state_dtype` selects the storage dtype of the delta-compressed
    # stacked client state for strategies with a ClientStateCodec
    # ("fp32"/None = identity, bitwise; "bf16" halves stacked-state
    # memory, tolerance-equal trajectories).
    window: int = 1
    eval_align: bool = False
    state_dtype: Optional[str] = None
    # out-of-core client state: "device" keeps the stacked state resident
    # on the accelerator (the bitwise default); "host" keeps the full
    # codec-encoded pool in host RAM (``repro.sim.state_pool``, optionally
    # split over `state_shards` contiguous row ranges) and moves only each
    # window's active-cohort rows host→device — gathered speculatively on
    # the prefetch producer thread, scattered back after the megastep — so
    # device memory scales with the active cohort, not the fleet size K.
    # `state_qclip` is the quantized state codecs' (int8/int4) symmetric
    # clip range for parameter-delta leaves.
    state_residency: str = "device"
    state_shards: int = 1
    state_qclip: float = 0.5
    # feature pass lowering: None = auto (Pallas kernel above the ops.py
    # size threshold on TPU, jnp otherwise); True/False force it.  The
    # interpret flag runs the kernel through the Pallas interpreter — the
    # CPU-CI hook for exercising the kernel path in equivalence tests.
    feature_kernel: Optional[bool] = None
    feature_kernel_interpret: bool = False
    # server-fold lowering: "sequential" replays the per-arrival fold scan
    # (the bitwise oracle and the default); "associative" requires the
    # strategy's affine fold form (`Strategy.build_fold_affine`) and runs
    # the tick's folds as one log-depth prefix scan — same math, fp
    # reassociation aside; "auto" picks associative on accelerators when
    # the strategy provides the affine form, sequential otherwise.
    # `fold_kernel` mirrors `feature_kernel` for the linear-scan lowering
    # of the affine fold (None = per-leaf auto via
    # kernels.linear_scan.ops.use_kernel_default; the interpret flag is
    # the CPU-CI hook for the Pallas path).
    fold_mode: str = "sequential"
    fold_kernel: Optional[bool] = None
    fold_kernel_interpret: bool = False
    # upload compression: the client→server wire delta of each arrival is
    # passed through an UploadCodec ("identity" | "topk_sparse" |
    # "random_mask" | "quantized_delta" — repro.core.algorithms.common)
    # inside the jitted tick, and its simulated wire cost feeds the
    # scheduler's bandwidth-metered delay draws (DeviceProfile.
    # bandwidth_bytes_per_s).  `upload_frac` is the kept-coordinate
    # fraction (topk_sparse / random_mask); `upload_bits` the
    # quantized_delta integer width.  "identity" is bitwise passthrough.
    upload_codec: str = "identity"
    upload_frac: float = 0.1
    upload_bits: int = 8
    # server-side graceful degradation (the chaos layer's admission
    # control, applied inside the jitted tick as fold masks so megastep /
    # associative folds / oracles stay equivalent under faults):
    # non-finite uploads are ALWAYS rejected when any client carries a
    # FaultSpec; `max_staleness`, when set, additionally bounds the
    # per-arrival staleness (iterations since the client's previous
    # fold) — `staleness_policy` picks between rejecting the upload
    # outright ("reject") and folding it at weight
    # max_staleness/staleness ("downweight").  `max_delta_norm`, when
    # set, clips each admitted wire delta to that global L2 norm.
    max_staleness: Optional[float] = None
    staleness_policy: str = "reject"  # "reject" | "downweight"
    max_delta_norm: Optional[float] = None


@dataclasses.dataclass
class HistoryPoint:
    """One eval of a run.  ``wall_time`` is the run's wall seconds when the
    eval was *dispatched* (its predict is enqueued and its metrics are
    read after the run), not when its answers were ready."""

    global_iter: int
    sim_time: float
    wall_time: float
    metrics: Dict[str, float]


# ---------------------------------------------------------------------------
# Strategy protocol
# ---------------------------------------------------------------------------


class Strategy:
    """Algorithm plug-in: local-update + aggregation rules, nothing else.

    ``build_*`` methods return *traceable* functions (no ``jax.jit`` — the
    engine jits the whole tick).  Per-member signatures:

    * local(carry, bcast, xs, ys, delay, n_vis, t_arr)
          -> (carry', upload, telemetry)
      where ``telemetry`` maps each name in :meth:`telemetry_slots` to a
      per-client scalar (the engine reduces them to masked cohort means
      inside the tick — the in-scan telemetry rows)
    * fold(server, upload, idx, n_vis, t_arr) -> (server', received)
    * merge(carry, received) -> carry   (post-fold download to the client)
    * finalize(server) -> server        (sync barrier, e.g. FedAvg average)
    """

    name: str = "base"
    schedule: str = "async"  # "async" | "sync" | "sweep"
    uses_dropout: bool = True
    pooled: bool = False  # Global baseline: one virtual member, pooled data
    eval_per_client: bool = False  # Local baseline: per-client eval params
    # whether build_fold_affine's closed form stays exact when the chaos
    # layer injects duplicate deliveries / rejected uploads (fedbuff's
    # flush cummax is not composable under the dup coefficient squaring,
    # so it declines and the engine falls back to the sequential scan)
    fold_affine_supports_faults: bool = True

    # -- telemetry -------------------------------------------------------
    def telemetry_slots(self, cfg: RunConfig) -> Tuple[str, ...]:
        """Names of the per-client scalars ``local`` emits for the
        in-scan telemetry accumulator.  Every strategy's local round
        already computes its training loss, so ``train_loss`` is the
        default slot; override to add algorithm-specific signals (the
        values must be keys of the telemetry dict ``local`` returns)."""
        return ("train_loss",)

    def server_telemetry_slots(self, cfg: RunConfig) -> Tuple[str, ...]:
        """Names of post-fold *server* scalars appended to the in-scan
        telemetry row (e.g. fedbuff's buffer fill).  The engine inserts
        its own ``folds_per_tick`` slot between the client slots and
        these; values come from :meth:`build_server_telemetry`."""
        return ()

    def build_server_telemetry(self, model, cfg: RunConfig):
        """Optional traceable ``server -> {slot: scalar}`` evaluated after
        the tick's folds.  Required (non-None) exactly when
        :meth:`server_telemetry_slots` is non-empty."""
        return None

    # -- state construction ---------------------------------------------
    def init_client(self, model, cfg: RunConfig, w0,
                    client: Optional[SimClient]):
        raise NotImplementedError

    def build_init_client(self, model, cfg: RunConfig):
        """Optional traceable ``(w0, n0) -> client state`` for the batched
        stacked init: one vmapped jit builds every row of the stacked state
        instead of K+1 eager ``init_client`` calls + ``tree_stack`` (the
        dominant per-run setup cost at large K).  ``n0`` is the client's
        ``stream.visible(0)`` sample count.  Return None to fall back to
        the per-client path (strategies whose init needs host-side state,
        e.g. per-client PRNG model inits)."""
        return None

    def init_server(self, model, cfg_model, cfg: RunConfig, w0,
                    clients: Sequence[SimClient],
                    active: Sequence[SimClient]):
        return {}

    def state_codec(self, model, cfg: RunConfig, w0):
        """Optional ``ClientStateCodec`` for the stacked client state
        (``repro.core.algorithms.common``).  None (the default, and the
        required answer for ``state_dtype in (None, "fp32")``) stores the
        fp32 master state directly — the bitwise-replayable path."""
        return None

    def upload_codec_view(self, model, cfg: RunConfig):
        """Optional ``(extract, rebuild)`` pair exposing the strategy's
        *wire delta* — the model-parameter-shaped pytree each arrival
        actually transmits — for lossy upload compression
        (``RunConfig.upload_codec``):

        * ``extract(upload, carry0, bcast) -> delta``: the transmitted
          delta, as a pytree shaped like the model parameters
          (``carry0`` is the client's pre-round carry, ``bcast`` the
          tick's server broadcast — whichever baseline the upload is
          relative to);
        * ``rebuild(upload, delta, carry0, bcast) -> upload'``: the
          upload with its delta replaced by the lossily reconstructed
          one (non-delta fields, e.g. version stamps, pass through).

        Both must be traceable and per-arrival (the engine vmaps them
        over the cohort axis).  Return None (the default) when the
        strategy has no compressible upload (the Local/Global sweep
        baselines) — a non-identity ``upload_codec`` then fails fast."""
        return None

    # -- traceable pieces ------------------------------------------------
    def build_local(self, model, cfg: RunConfig):
        raise NotImplementedError

    def build_fold(self, model, cfg_model, cfg: RunConfig):
        return None  # no server (Local baseline)

    def build_fold_affine(self, model, cfg_model, cfg: RunConfig):
        """Optional *parallel form* of :meth:`build_fold` for strategies
        whose fold is affine in the server weights: return None to
        decline (the sequential scan is always available), else a triple
        ``(carrier, coeffs, unfold)`` of traceables —

        * ``carrier(server) -> h0``: the affine part of the server state
          (a pytree; the recurrence ``h_s = a_s * h_{s-1} + b_s`` runs
          over its leaves);
        * ``coeffs(server, uploads, idx, n_vis, t_arr, mask) ->
          (a, b, aux)``: per-arrival coefficients computed from the
          already-vmapped upload stream — ``a`` is ``(S,)``, ``b`` a
          pytree of ``(S, ...)`` leaves matching ``carrier``'s structure,
          and masked padding slots MUST be the identity (a=1, b=0);
          ``aux`` carries any closed-form byproducts to ``unfold``;
        * ``unfold(server, h, aux, uploads, idx, n_vis, t_arr, mask) ->
          (server', received)``: rebuild the post-tick server from the
          inclusive prefix states ``h`` (pytree of ``(S, ...)``) and the
          per-arrival ``received`` stream consumed by the vmapped merge.

        The engine executes the recurrence with
        ``repro.kernels.linear_scan.ops.fold_prefix`` (associative scan /
        Pallas kernel) when ``RunConfig.fold_mode`` asks for it."""
        return None

    def build_merge(self, model, cfg: RunConfig):
        return lambda carry, received: carry

    def build_finalize(self, model, cfg: RunConfig):
        return None

    def server_broadcast(self, server):
        return server

    # -- evaluation ------------------------------------------------------
    def eval_params(self, server, stacked_clients):
        """Params to evaluate: central model, or stacked per-client params
        when ``eval_per_client``."""
        return server["w"]

    # -- pooled-data hook (Global baseline only) -------------------------
    def pooled_batches(self, clients, t: int, cfg: RunConfig):
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Host-side batch construction
# ---------------------------------------------------------------------------


def pad_batch(x: Array, y: Array, size: int, template_x: Array,
              template_y: Array) -> Tuple[Array, Array]:
    """Force (x, y) to exactly ``size`` rows (keeps jit shapes static).

    Short draws are padded by cycling the drawn rows (``np.resize`` —
    one strided copy instead of the old O(reps) concatenate loop); an
    *empty* draw (a client whose visible window is empty) yields all-zero
    rows instead of the historical division-by-zero crash.  ``template_*``
    supply the row shape/dtype for the empty case.
    """
    if len(x) == 0:
        return (np.zeros((size,) + template_x.shape[1:], template_x.dtype),
                np.zeros((size,) + template_y.shape[1:], template_y.dtype))
    if len(x) < size:
        x = np.resize(x, (size,) + x.shape[1:])
        y = np.resize(y, (size,) + y.shape[1:])
    return x[:size], y[:size]


def stack_batches(stream: OnlineStream, t: int, batch_size: int,
                  n_steps: int) -> Tuple[Array, Array]:
    """(n_steps, batch_size, ...) minibatches from one client's stream.

    Consumes the same rng draws as ``OnlineStream.batch_into`` — the
    engine's staging-buffer path and this allocating path are
    interchangeable without perturbing the trajectory.
    """
    xs, ys = [], []
    for _ in range(n_steps):
        x, y = pad_batch(*stream.batch(t, batch_size), batch_size,
                         stream.x, stream.y)
        xs.append(x)
        ys.append(y)
    return np.stack(xs), np.stack(ys)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


def _live_device_bytes() -> int:
    """Total bytes of live jax arrays (process-wide) — the memory column
    sampled around dispatches for ``stats["peak_live_device_bytes"]``.
    Best-effort: 0 when the runtime can't enumerate live buffers."""
    try:
        return sum(int(getattr(a, "nbytes", 0)) for a in jax.live_arrays())
    except Exception:  # noqa: BLE001 — observability must never kill a run
        return 0


def run_strategy(
    strategy: Strategy,
    model,
    cfg_model,
    clients: Sequence[SimClient],
    cfg: RunConfig,
    *,
    max_cohort: Optional[int] = None,
    trace: Optional[List] = None,
    stats: Optional[Dict] = None,
    telemetry: Optional[TelemetryLog] = None,
    prefetch: Optional[bool] = None,
    window: Optional[int] = None,
    mesh: Union[str, None, Mesh] = "auto",
    checkpoint_path: Optional[str] = None,
    checkpoint_every: Optional[int] = None,
    resume_from: Optional[str] = None,
) -> List[HistoryPoint]:
    """Run one algorithm through the cohort engine.

    ``max_cohort`` caps the clients per tick (1 reproduces the per-arrival
    dispatch pattern; None batches every pending arrival).  ``window``
    overrides ``cfg.window``: the number of consecutive async ticks fused
    into one megastep dispatch (``jit(lax.scan(tick))`` over a stacked
    window block); evals and ``trace`` samples land on window boundaries
    unless ``cfg.eval_align`` splits windows at the eval cadence.
    ``telemetry``, when a :class:`~repro.sim.telemetry.TelemetryLog`, is
    filled with one per-tick record (in-scan train-loss + participation /
    staleness) regardless of window size — finalized when the run
    returns.  ``trace``, when a list, receives ``(t,
    eval-params-as-numpy)`` after every dispatch — the hook the
    equivalence tests use.  ``stats``, when a dict, is filled with
    ``{"ticks", "windows", "iters", "sim_time"}`` counters plus the
    per-phase wall breakdown ``{"host_build_s", "device_s", "eval_s",
    "window_wait_s", "run_setup_s"}`` (each the summed seconds of one
    ``repro.*`` span, see :func:`repro.sim.telemetry.span`), the count of
    full garbage collections in the run (``"gc_full_collections"``),
    the ``{"prefetch", "devices", "window", "state_dtype",
    "tick_cache_size"}`` run descriptors, the ``{"stacked_state_bytes",
    "peak_live_device_bytes"}`` memory columns (benchmark hooks), and the
    telemetry summary (``train_loss_final`` etc.).  ``prefetch``
    overrides ``cfg.prefetch`` (None → adaptive: on for accelerators and
    >=4-core hosts).  ``mesh="auto"`` shards the client axis over every
    local device (``repro.common.sharding.data_mesh``); pass None to
    force the single-device path or an explicit 1-D ``data`` Mesh.

    ``checkpoint_path`` (async schedules only) writes a resumable
    full-run snapshot — device state via ``repro.checkpoint`` plus the
    host event-stream cursor (scheduler rng/heap/fault counters, stream
    rngs, staleness meter) — every ``checkpoint_every`` iterations
    (default: ``cfg.eval_every``).  ``resume_from`` restores one and
    continues: the resumed run replays the remaining arrival stream, and
    therefore the final weights, bit-for-bit against an uninterrupted
    run (its ``history`` covers only post-resume evals).
    """
    with span("repro.run"), FullCollections() as full_collections, \
            contextlib.ExitStack() as setup:
        # everything up to the first window request is the run's set-up:
        # `setup.close()` ends it there
        phases = types.SimpleNamespace(run_setup_s=0.0, window_wait_s=0.0,
                                       device_s=0.0, eval_s=0.0)
        setup.enter_context(span("repro.run.setup", phases, "run_setup_s"))
        clients = list(clients)
        K = len(clients)
        # client cids index rows of the stacked state pytree (and the
        # server's per-client count arrays): require the dense 0..K-1
        # layout up front — JAX gather/scatter would clamp a stray cid
        # silently, not raise
        if [c.cid for c in clients] != list(range(K)):
            raise ValueError(
                "run_strategy requires clients with cid == position "
                f"(0..{K - 1}); got {[c.cid for c in clients]}"
            )
        if mesh == "auto":
            mesh = sharding_lib.data_mesh()
        E, B = cfg.local_epochs, cfg.batch_size
        max_cohort = max_cohort if max_cohort is not None else cfg.max_cohort
        W = max(1, int(window if window is not None else cfg.window))
        # fail-fast validation before any compile/run cost: a typo'd dtype,
        # task, or workload name must raise readably, not ride silently into
        # the stats/BENCH columns (or report the wrong task's metrics)
        dtypes_lib.resolve_state_dtype(cfg.state_dtype)
        if cfg.state_residency not in ("device", "host"):
            raise ValueError(
                f"unknown state_residency {cfg.state_residency!r}; "
                "accepted: 'device' | 'host'")
        if cfg.state_residency == "host" and strategy.schedule != "async":
            raise ValueError(
                "state_residency='host' is supported for async schedules only "
                f"({strategy.name!r} is {strategy.schedule!r}): the host pool "
                "rides the windowed gather/scatter tick path")
        if cfg.state_residency == "host" and (strategy.eval_per_client
                                              or strategy.pooled):
            raise ValueError(
                f"state_residency='host' cannot serve {strategy.name!r}: "
                "per-client / pooled evaluation reads the full stacked state, "
                "which a host-resident pool keeps off-device")
        if cfg.state_shards < 1:
            raise ValueError(
                f"state_shards must be >= 1, got {cfg.state_shards}")
        if cfg.eval_every < 0:
            raise ValueError(
                f"eval_every must be >= 0 (0 disables evaluation), "
                f"got {cfg.eval_every}")
        eval_report = resolve_eval_report(cfg)
        # chaos layer: any client carrying an active FaultSpec switches the
        # compiled tick to fault-aware mode (crash-restart state resets, wire
        # corruption, duplicate double-folds); the server admission guards
        # activate with it or with the explicit cfg knobs.  Both are
        # compile-time flags, so a fault-free config traces the exact
        # pre-chaos tick and replays bitwise.
        faults_on = any(
            c.profile.faults is not None and c.profile.faults.active
            for c in clients)
        if cfg.staleness_policy not in ("reject", "downweight"):
            raise ValueError(
                f"unknown staleness_policy {cfg.staleness_policy!r}; "
                "accepted: 'reject' | 'downweight'")
        guards = (cfg.max_staleness is not None
                  or cfg.max_delta_norm is not None)
        chaos = faults_on or guards
        if (checkpoint_path is not None or resume_from is not None) \
                and strategy.schedule != "async":
            raise ValueError(
                "run checkpointing / resume is supported for async schedules "
                f"only ({strategy.name!r} is {strategy.schedule!r})")
        # ... and so must an unknown fold_mode, or fold_mode="associative"
        # with a strategy that declines the affine fold form (under faults,
        # additionally one whose closed form is not dup/reject-composable)
        compile_lib.resolve_fold_affine(strategy, model, cfg_model, cfg,
                                        faults_on=faults_on)
        # ... and an unknown upload codec / out-of-range knobs, or a lossy
        # codec on a strategy with no compressible upload.  (Imported here:
        # the strategy modules import Strategy from this module, so a
        # top-level import of repro.core would be circular.)
        from repro.core.algorithms.common import resolve_upload_codec

        ucodec = resolve_upload_codec(cfg)
        uview = strategy.upload_codec_view(model, cfg)
        if not ucodec.identity and uview is None:
            raise ValueError(
                f"upload_codec={cfg.upload_codec!r} requires a strategy with "
                f"a compressible upload, but {strategy.name!r} provides no "
                "upload_codec_view (the Local/Global sweep baselines upload "
                "nothing)")
        if chaos and strategy.schedule != "sweep" and uview is None:
            raise ValueError(
                "fault injection / admission guards act on the strategy's "
                f"wire-delta view, but {strategy.name!r} provides no "
                "upload_codec_view")
        if faults_on and strategy.schedule == "async" \
                and strategy.build_init_client(model, cfg) is None:
            raise ValueError(
                f"fault injection needs {strategy.name!r} to provide "
                "build_init_client: crash-restart rebuilds the crashed "
                "client's state row inside the jitted tick")
        w0 = model.init(jax.random.PRNGKey(cfg.seed))
        codec = strategy.state_codec(model, cfg, w0)
        # simulated wire cost of one arrival's (encoded) upload — a pure
        # function of codec config and model leaf shapes, fed to the
        # schedulers' bandwidth-metered delay draws.  Strategies without an
        # upload (sweep baselines) transmit nothing.
        upload_bytes = ucodec.tree_bytes(w0) if uview is not None else 0.0
        client_slots = tuple(strategy.telemetry_slots(cfg))
        server_slots = tuple(strategy.server_telemetry_slots(cfg))
        # the engine-owned fold-depth slot rides between the two blocks;
        # chaos runs append the admission counters to the in-scan row (the
        # condition mirrors tick_body's: guards need a fold + a wire view)
        chaos_tick = (
            chaos and uview is not None
            and strategy.build_fold(model, cfg_model, cfg) is not None)
        slots = client_slots + ("folds_per_tick",) + server_slots
        if chaos_tick:
            slots = slots + ("rejected_per_tick", "clipped_per_tick")
        drop = cfg.dropout_frac if strategy.uses_dropout else 0.0
        skip = cfg.periodic_dropout if strategy.uses_dropout else 0.0

        if strategy.schedule == "async":
            sched = AsyncScheduler(
                clients, seed=cfg.seed, dropout_frac=drop, skip_prob=skip,
                init_work=B, round_work=E * B,
                sim_time_budget=cfg.sim_time_budget,
                upload_bytes=upload_bytes,
            )
            active = sched.active
            pad = max(1, min(max_cohort or len(active), max(len(active), 1)))
        elif strategy.schedule == "sync":
            sched = SyncScheduler(
                clients, seed=cfg.seed, dropout_frac=drop, skip_prob=skip,
                participation=cfg.participation, round_work=E * B,
                upload_bytes=upload_bytes,
            )
            active = sched.active
            pad = sched.m
        else:  # sweep
            sched = SweepScheduler(clients)
            active = sched.active
            pad = 1 if strategy.pooled else K

        n_members = 1 if strategy.pooled else K
        members = [None] if strategy.pooled else clients
        scratch = n_members  # the scratch row targeted by padded slots
        n_rows = n_members + 1
        if mesh is not None:
            # extra scratch rows so the client axis divides the mesh evenly
            D = mesh.devices.size
            n_rows = -(-n_rows // D) * D

        def _n0(c: Optional[SimClient]) -> float:
            return float(c.stream.visible(0)) if c is not None else 0.0

        with span("repro.setup.init_state"):
            init_batched = compile_lib.batched_init_fn(strategy, model, cfg)
            pool = None
            if cfg.state_residency == "host":
                if init_batched is None:
                    raise ValueError(
                        f"state_residency='host' needs {strategy.name!r} to "
                        "provide build_init_client: the pool is filled by "
                        "chunked batched init (a device-stacked init of all K "
                        "rows is exactly what the host pool exists to avoid)")
                from repro.sim.state_pool import HostStatePool

                storage = dtypes_lib.resolve_state_storage(cfg.state_dtype)
                packed = (storage is not None and codec is not None
                          and storage.pool_bits == 4)
                tmpl = init_batched(
                    w0, jnp.asarray(np.array([_n0(members[0])], np.float32)))
                if codec is not None:
                    tmpl = codec.encode(tmpl)
                pool = HostStatePool(
                    jax.tree.map(lambda x: np.asarray(x[0]), tmpl), n_members,
                    packed=packed, shards=min(cfg.state_shards, n_members))
                # chunked init: device footprint of one chunk at a time,
                # encoded and streamed into the pool (the K=10^6 setup path)
                CHUNK = 4096
                s = 0
                while s < n_members:
                    e = min(s + CHUNK, n_members)
                    n0c = np.array([_n0(c) for c in members[s:e]], np.float32)
                    chunk = init_batched(w0, jnp.asarray(n0c))
                    if codec is not None:
                        chunk = codec.encode(chunk)
                    pool.write_block(s, jax.tree.map(np.asarray, chunk))
                    s = e
                stacked = None  # no device-resident stack: per-window blocks
            elif init_batched is not None:
                n0s = np.array([_n0(c) for c in members]
                               + [_n0(members[0])] * (n_rows - n_members),
                               np.float32)
                stacked = init_batched(w0, jnp.asarray(n0s))
            else:
                from repro.common.pytree import tree_stack

                states = [strategy.init_client(model, cfg, w0, c)
                          for c in members]
                states += [strategy.init_client(model, cfg, w0, members[0])
                           ] * (n_rows - n_members)
                stacked = tree_stack(states)
            if codec is not None and stacked is not None:
                # one-time: the state lives compressed
                stacked = codec.encode(stacked)
            server = strategy.init_server(model, cfg_model, cfg, w0, clients,
                                          active)
            if mesh is not None:
                if stacked is not None:
                    stacked = jax.device_put(stacked, jax.tree.map(
                        lambda x: sharding_lib.client_sharding(x.shape, mesh),
                        stacked))
                server = jax.device_put(server, sharding_lib.replicated(mesh))
        windowed = strategy.schedule == "async"
        with span("repro.setup.tick_fn"):
            tick_fn = compile_lib.tick_fn(
                strategy, model, cfg_model, cfg, K, mesh, windowed=windowed,
                codec=codec, slots=client_slots, server_slots=server_slots,
                faults_on=faults_on)
        # eval_every=0 disables evaluation entirely: no padded [K, n_max]
        # test tensor ever lands on device (the K-sweep bench path, where
        # device memory must stay bounded by the active cohort, not K)
        evaluator = None
        if cfg.eval_every > 0:
            with span("repro.setup.evaluator"):
                evaluator = Evaluator(model, clients, eval_report,
                                      strategy.eval_per_client)
        telem = telemetry if telemetry is not None else TelemetryLog(slots)
        if telem.slots != slots:
            telem.slots = slots  # a caller's log adopts the run's slots
        by_id = {c.cid: c for c in clients}

        def transfer(name, arr):
            sh = sharding_lib.client_sharding(arr.shape, mesh)
            return jnp.asarray(arr) if sh is None else jax.device_put(arr, sh)

        def window_transfer(name, arr):
            sh = sharding_lib.window_sharding(arr.shape, mesh)
            return jnp.asarray(arr) if sh is None else jax.device_put(arr, sh)

        builder = TickBuilder(
            by_id=by_id, batch_size=B, local_epochs=E, scratch=scratch,
            pad=pad,
            pooled=strategy.pooled, transfer=transfer,
            window_transfer=window_transfer, state_pool=pool,
        )
        # under host residency the device-side state is the per-window cohort
        # block, not the [K, ...] stack: the column reports the largest block
        # actually dispatched (updated in `dispatch`), so it is what it claims
        # to be — live device bytes of client state — in both modes
        stacked_state_bytes = 0 if pool is not None else sum(
            int(x.size) * jnp.dtype(x.dtype).itemsize
            for x in jax.tree.leaves(stacked))
        peak_live = _live_device_bytes()

        history: List[HistoryPoint] = []
        pending_evals: List[Tuple[int, float, float, Any]] = []
        n_ticks, n_windows, t, sim_time = 0, 0, 0, 0.0
        n_uploads = 0  # folded arrivals (each transmits one encoded delta)
        t0 = time.perf_counter()

        def eval_params():
            if pool is not None:  # host residency: central-model eval only
                return strategy.eval_params(server, None)
            members_view = jax.tree.map(lambda x: x[:n_members], stacked)
            if codec is not None and (strategy.eval_per_client
                                      or strategy.pooled):
                members_view = codec.decode(members_view)
            return strategy.eval_params(server, members_view)

        def record(t: int, sim_time: float):
            if evaluator is None:
                return
            with span("repro.eval.predict", phases, "eval_s"):
                preds = evaluator.predict_device(eval_params())
                pending_evals.append((t, sim_time, time.perf_counter() - t0,
                                      preds))

        def dispatch(pt):
            nonlocal stacked, server, n_ticks, n_windows, peak_live, \
                stacked_state_bytes
            with span("repro.dispatch", phases, "device_s",
                      bucket=pt.arrays[0].shape[-1], n_ticks=pt.n_ticks,
                      window=n_windows):
                if pool is not None:
                    # host residency: repair the speculative gather (rows
                    # written by scatters that landed after it), move the
                    # cohort block to device, run the megastep on it as the
                    # stacked carry, and scatter the updated member rows back
                    # into the pool
                    pool.patch(pt.block, pt.block_cids, pt.gather_seq)
                    block = jax.tree.map(lambda x: transfer("block", x),
                                         pt.block)
                    stacked_state_bytes = max(stacked_state_bytes, sum(
                        int(x.size) * jnp.dtype(x.dtype).itemsize
                        for x in jax.tree.leaves(block)))
                    block, server, tel = tick_fn(block, server, *pt.arrays)
                    with span("repro.dispatch.sync"):
                        jax.block_until_ready((block, server))
                    pool.scatter(pt.block_cids[:pt.block_rows],
                                 jax.tree.map(np.asarray, block))
                else:
                    stacked, server, tel = tick_fn(stacked, server, *pt.arrays)
                    with span("repro.dispatch.sync"):
                        jax.block_until_ready((stacked, server))
                with span("repro.telemetry.append"):
                    telem.append(pt, tel)
            n_ticks += pt.n_ticks
            n_windows += 1
            # steady-state live-set snapshot, off the hot path
            if n_windows <= 2:
                peak_live = max(peak_live, _live_device_bytes())

        use_prefetch = False
        resume_t = 0
        if strategy.schedule == "async":
            # a client with an empty local split (visible == 0 forever) can
            # never train: its arrivals are dropped so fabricated zero batches
            # are never folded in (FedAsync mixes at full weight, without the
            # n_vis/N guard ASO-Fed has)
            trainable = {c.cid for c in active if c.stream.n > 0}
            if resume_from is not None:
                from repro import checkpoint as ckpt_lib

                stacked, server, host = ckpt_lib.load_run_state(
                    resume_from, stacked, server, pool=pool)
                if host.get("strategy") != strategy.name \
                        or int(host.get("seed", cfg.seed)) != cfg.seed:
                    raise ValueError(
                        f"snapshot at {resume_from!r} was written by "
                        f"strategy={host.get('strategy')!r} "
                        f"seed={host.get('seed')}; this run is "
                        f"{strategy.name!r} seed={cfg.seed}")
                if mesh is not None:
                    if stacked is not None:
                        stacked = jax.device_put(stacked, jax.tree.map(
                            lambda x: sharding_lib.client_sharding(
                                x.shape, mesh), stacked))
                    server = jax.device_put(server,
                                            sharding_lib.replicated(mesh))
                sched.load_state_dict(host["sched"])
                for cid_s, st_rng in host["streams"].items():
                    by_id[int(cid_s)].stream.set_rng_state(st_rng)
                builder.staleness.load_state_dict(host["staleness"])
                resume_t = int(host["t"])
                t = resume_t
                sim_time = float(host["sim_time"])
            # adaptive default: the prefetch thread overlaps host batch
            # building with device execution, which is a pure win on
            # accelerators and multi-core hosts — but on <4-core CPU boxes
            # the builder steals cycles from XLA itself and the "overlap" is
            # negative-sum.  Trajectories are bit-identical either way (the
            # speculation contract), so the default is free to choose.
            try:  # affinity respects container/cgroup CPU limits; cpu_count
                ncpu = len(os.sched_getaffinity(0))  # does not
            except AttributeError:
                ncpu = os.cpu_count() or 1
            use_prefetch = (prefetch if prefetch is not None
                            else cfg.prefetch if cfg.prefetch is not None
                            else jax.default_backend() != "cpu" or ncpu >= 4)

            def produce():
                """Pop + filter + build each window (worker thread when
                prefetching).  Mirrors the consuming loop's termination logic
                exactly, so at most the single in-flight speculative peek is
                ever un-committed.  ``total_limit`` caps *popped* arrivals at
                the remaining iteration budget — for W == 1 this is exactly
                the old per-tick ``peek_tick(min(pad, T - tp))`` stream.

                A window is split into maximal runs of *same-bucket* ticks
                (one fused ``lax.scan`` block per run): a tick must execute
                at exactly the shape bucket it would ride at W == 1, because
                XLA's lowering is shape-dependent and inflating a small tick
                to a larger bucket would break the window-on/off bitwise
                replay.  In the steady state arrivals-per-tick is stable, so
                runs span whole windows; bucket switches (the first
                full-cohort tick, the drained tail, churn) cost one extra
                dispatch each — never a wrong bit.  With ``cfg.eval_align``
                windows are first split at ``eval_every`` fold boundaries
                (``repro.sim.telemetry.split_at_evals``), so the consuming
                loop's eval check fires at exactly the ticks a window=1 run
                would evaluate after — a dispatch-count trade, still never a
                wrong bit.
                """
                tp = resume_t
                sim_prod = float(sim_time)
                # the iteration budget advances per *fold*: charge it only
                # for trainable arrivals, so every in-window tick limit
                # equals the one a window=1 producer would compute (dropped
                # empty-split clients must not perturb tick membership)
                kept_count = lambda tk: sum(  # noqa: E731
                    a.cid in trainable for a in tk)
                while tp < cfg.T:
                    snap = None
                    if checkpoint_path is not None:
                        # full host cursor, captured at the only clean point:
                        # the previous window is committed (no speculation in
                        # flight) and no stream rng draw for the upcoming
                        # window has been consumed.  It rides the window's
                        # first PreparedTick to the consumer, which persists
                        # it together with the device state *before*
                        # dispatching that tick.
                        snap = {
                            "t": tp, "sim_time": sim_prod,
                            "strategy": strategy.name, "seed": cfg.seed,
                            "state_residency": cfg.state_residency,
                            "sched": sched.state_dict(),
                            "streams": {str(c.cid): c.stream.rng_state()
                                        for c in active},
                            "staleness": builder.staleness.state_dict(),
                        }
                    with span("repro.sched.peek_window"):
                        ticks = sched.peek_window(W, pad,
                                                  total_limit=cfg.T - tp,
                                                  count=kept_count)
                        sched.commit()
                    if not ticks:
                        break  # drained or over the simulated-time budget
                    kept = [[a for a in tk if a.cid in trainable]
                            for tk in ticks]
                    kept = [tk for tk in kept if tk]
                    if not kept:
                        continue  # window held only empty-split clients
                    if cfg.eval_align and W > 1 and cfg.eval_every > 0:
                        segments = split_at_evals(kept, tp, cfg.eval_every,
                                                  count=kept_count)
                    else:
                        segments = [kept]
                    for seg in segments:
                        groups: List[Tuple[int, List]] = []
                        for tk in seg:
                            b = bucket_size(len(tk), pad)
                            if groups and groups[-1][0] == b:
                                groups[-1][1].append(tk)
                            else:
                                groups.append((b, [tk]))
                        # each same-bucket run is split greedily into exact
                        # power-of-two chunks (8+2 instead of 16 with 6
                        # masked ticks): a fully-masked padding tick costs a
                        # whole bucket's compute, an extra dispatch costs
                        # microseconds.  Blocks are built only as the queue
                        # drains: the staging slots rotate over NSLOTS
                        # buffers, so at most (consumer's current + queued +
                        # being-built) blocks are in flight.
                        for _, g in groups:
                            i = 0
                            while i < len(g):
                                n = 1 << ((len(g) - i).bit_length() - 1)
                                chunk = g[i:i + n]
                                i += n
                                pt = builder.build_window(
                                    chunk, t_start=tp, window=W,
                                    sim_time=chunk[-1][-1].time)
                                tp = pt.t_end
                                sim_prod = pt.sim_time
                                if snap is not None:
                                    pt.host_snapshot = snap
                                    snap = None
                                yield pt

            if not trainable:
                source = iter(())
            elif use_prefetch:
                source = TickPrefetcher(produce(), depth=1)
            else:
                source = produce()
            next_eval = (resume_t // cfg.eval_every + 1) * cfg.eval_every \
                if cfg.eval_every > 0 else cfg.T + 1
            ckpt_every = int(checkpoint_every) if checkpoint_every \
                else (cfg.eval_every or cfg.T)
            next_ckpt = resume_t + ckpt_every if checkpoint_path is not None \
                else None
            setup.close()
            try:
                while True:
                    with span("repro.window.wait", phases, "window_wait_s"):
                        pt = next(source, None)
                    if pt is None:
                        break
                    if (next_ckpt is not None and pt.host_snapshot is not None
                            and pt.host_snapshot["t"] >= next_ckpt):
                        # write-before-dispatch: the device state on disk is
                        # exactly the state the host cursor says it is (the
                        # snapshot's t counts the folds already applied)
                        from repro import checkpoint as ckpt_lib

                        # under host residency the pool is the client-state
                        # payload: at this point every earlier window has
                        # scattered back (dispatch is synchronous on this
                        # thread), so the pool holds exactly the state after
                        # the snapshot's t folds
                        with span("repro.checkpoint.save"):
                            ckpt_lib.save_run_state(checkpoint_path, stacked,
                                                    server, pt.host_snapshot,
                                                    pool=pool)
                        next_ckpt = pt.host_snapshot["t"] + ckpt_every
                    dispatch(pt)
                    t = pt.t_end
                    sim_time = pt.sim_time
                    if trace is not None:
                        trace.append(
                            (t, jax.tree.map(np.asarray, eval_params())))
                    if t >= next_eval or t >= cfg.T:
                        record(t, sim_time)
                        while next_eval <= t:
                            next_eval += cfg.eval_every
            finally:
                if isinstance(source, TickPrefetcher):
                    source.close()
        else:
            setup.close()
            for t in range(1, cfg.T + 1):
                if (strategy.schedule == "sync" and cfg.sim_time_budget
                        and sim_time > cfg.sim_time_budget):
                    break
                arrivals, round_time = sched.next_round(now=sim_time)
                if not arrivals:
                    if strategy.schedule == "sync":
                        if not np.isfinite(round_time):
                            break  # fleet retired: no trace ever rejoins
                        # every participant skipped (round_time 0), or the
                        # whole fleet is off-window: the barrier still waits
                        # out the gap to the earliest rejoin edge
                        sim_time += round_time
                    continue
                pooled = (strategy.pooled_batches(clients, t, cfg)
                          if strategy.pooled else None)
                if strategy.pooled:
                    arrivals = arrivals[:1]
                # advance=False: a sync/sweep round's telemetry stamp is the
                # round index t itself, matching the eval history points
                pt = builder.build(arrivals, [t] * len(arrivals), sim_time,
                                   pooled_batch=pooled, advance=False)
                dispatch(pt)
                n_uploads += len(arrivals)
                sim_time = sim_time + round_time \
                    if strategy.schedule == "sync" else float(t)
                if trace is not None:
                    trace.append((t, jax.tree.map(np.asarray, eval_params())))
                if (cfg.eval_every > 0 and t % cfg.eval_every == 0) \
                        or t == cfg.T:
                    record(t, sim_time)

        for (te, ste, we, preds) in pending_evals:
            with span("repro.eval.extract", phases, "eval_s"):
                history.append(HistoryPoint(te, ste, we,
                                            evaluator.metrics_from(preds)))
        with span("repro.telemetry.finalize"):
            telem.finalize()
        with span("repro.builder.close"):
            builder.close()
        peak_live = max(peak_live, _live_device_bytes())
        if stats is not None:
            stats.update(
                ticks=n_ticks, windows=n_windows, iters=t, sim_time=sim_time,
                host_build_s=round(builder.host_build_s, 6),
                device_s=round(phases.device_s, 6),
                eval_s=round(phases.eval_s, 6),
                window_wait_s=round(phases.window_wait_s, 6),
                run_setup_s=round(phases.run_setup_s, 6),
                gc_full_collections=full_collections.count,
                prefetch=bool(use_prefetch),
                devices=int(mesh.devices.size) if mesh is not None else 1,
                window=W if strategy.schedule == "async" else 1,
                # "fp32" whenever no codec ran: a codec-less strategy stores
                # full-precision state regardless of what the config asked for
                state_dtype=(str(cfg.state_dtype) if codec is not None
                             else "fp32"),
                state_residency="host" if pool is not None else "device",
                stacked_state_bytes=int(stacked_state_bytes),
                peak_live_device_bytes=int(peak_live),
                # out-of-core accounting: host-pool footprint and the
                # gather/patch/scatter traffic (all zero under device
                # residency — the stack never moves)
                host_pool_bytes=int(pool.nbytes) if pool is not None else 0,
                gathered_rows=(int(pool.gathered_rows) if pool is not None
                               else 0),
                scattered_rows=int(pool.scattered_rows) if pool is not None
                else 0,
                gather_s=round(pool.gather_s, 6) if pool is not None else 0.0,
                scatter_s=(round(pool.scatter_s, 6) if pool is not None
                           else 0.0),
                # churn observability: per-arrival staleness (iterations since
                # the client's previous fold) and the fleet's mean on-fraction
                # over the simulated horizon, plus the scheduler's deferral /
                # retirement counters (always-on runs report 1.0 / 0 / 0)
                staleness_mean=round(builder.staleness.mean, 4),
                staleness_max=int(builder.staleness.max),
                availability_utilization=round(
                    availability_utilization(active, sim_time), 4),
                deferred_arrivals=int(getattr(sched, "deferred", 0)),
                retired_clients=int(getattr(sched, "retired", 0)),
                # chaos accounting: the scheduler's deterministic fault
                # counters (all 0 for fault-free configs)
                lost_uploads=int(getattr(sched, "lost", 0)),
                retried_uploads=int(getattr(sched, "retried", 0)),
                crashed_clients=int(getattr(sched, "crashed", 0)),
                duplicated_arrivals=int(getattr(sched, "duplicated", 0)),
                corrupted_arrivals=int(getattr(sched, "corrupted", 0)),
                # resource accounting: simulated wire bytes of one arrival's
                # encoded upload, and the run's total over every folded
                # arrival (async iterations each fold exactly one upload)
                upload_codec=ucodec.name,
                upload_bytes=float(upload_bytes),
                upload_bytes_total=float(upload_bytes) * (
                    t if strategy.schedule == "async" else n_uploads),
            )
            if resume_from is not None:
                stats["resumed_from_t"] = resume_t
            for k, v in telem.summary().items():
                stats[k] = round(v, 6) if isinstance(v, float) else v
            if chaos_tick:
                # the in-scan admission counters, totalled over the run
                stats["rejected_uploads"] = int(round(sum(
                    r.values.get("rejected_per_tick", 0.0)
                    for r in telem.records)))
                stats["clipped_uploads"] = int(round(sum(
                    r.values.get("clipped_per_tick", 0.0)
                    for r in telem.records)))
            if hasattr(tick_fn, "_cache_size"):
                stats["tick_cache_size"] = int(tick_fn._cache_size())
    return history
