"""Compile layer of the cohort engine: traceable tick bodies + fn caches.

This module owns everything between a :class:`~repro.sim.engine.Strategy`'s
traceable pieces and a dispatched ``jax.jit`` callable:

* :func:`tick_body` — the one-tick update ``(stacked, server, *inputs) ->
  (stacked, server, telemetry_row)``: gather (+ codec decode), vmapped
  local rounds (shard-mapped on a mesh), the sequential server fold scan,
  merge, masked scatter write-back (+ codec encode), and the in-scan
  telemetry reduction (masked cohort means of the per-client scalars the
  strategy's ``local`` emits — computed from values the round already
  produced, so the summaries cost no extra dispatches or transfers);
* :func:`build_megastep_fn` — ``lax.scan`` of the tick body over a fused
  ``[T_w]`` window axis, stacking one telemetry row per tick as the scan
  output (the accumulator rides the same dispatch as the window itself);
* the compiled-fn caches — one compilation per (model, strategy, config,
  shapes), shared across runs, NOT rebuilt per runner invocation.

Nothing here touches the scheduler, host staging buffers, or evaluation:
tick *building* lives in ``repro.sim.prefetch``, dispatch orchestration in
``repro.sim.engine``, metric extraction in ``repro.sim.telemetry`` /
``repro.sim.evaluation``.
"""
from __future__ import annotations

import dataclasses
import weakref
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.common import sharding as sharding_lib
from repro.common.pytree import tree_take, tree_scatter, tree_where
from repro.kernels.linear_scan import ops as scan_ops

_TICK_CACHE: Dict[Any, Tuple[Any, Any]] = {}
_PREDICT_CACHE: Dict[Any, Tuple[Any, Any]] = {}
_INIT_CACHE: Dict[Any, Tuple[Any, Any]] = {}


def mask_select(mask, new, old):
    """Per-member select: mask (P,) broadcast against stacked leaves."""
    return jax.tree.map(
        lambda n, o: jnp.where(mask.reshape(mask.shape + (1,) * (n.ndim - 1)),
                               n, o),
        new, old,
    )


def reduce_telemetry(tel, mask, slots: Sequence[str]):
    """(n_slots,) masked cohort means of the per-client telemetry scalars.

    One fixed reduction per tick, always at the tick's compile-time shape
    bucket — so a tick emits bit-identical telemetry whether it runs
    standalone or fused inside a window scan (the same invariance the
    stacked-state write-back relies on).
    """
    if not slots:
        return jnp.zeros((0,), jnp.float32)
    m32 = mask.astype(jnp.float32)
    cnt = jnp.maximum(jnp.sum(m32), 1.0)
    return jnp.stack([
        jnp.sum(jnp.where(mask, tel[s].astype(jnp.float32), 0.0)) / cnt
        for s in slots
    ])


def resolve_fold_affine(strategy, model, cfg_model, cfg, *,
                        faults_on: bool = False):
    """The affine fold triple to execute this run, or None for the
    sequential arrival-order scan.  Raises readably on an unknown
    ``fold_mode`` and on a forced-associative run whose strategy declines
    the affine form — the engine calls this in its fail-fast validation
    before any compile cost is paid.

    ``"auto"`` is conservative: associative only when the strategy
    provides the affine form AND the backend is an accelerator — on CPU
    the sequential scan is the bitwise contract and small fold streams
    don't pay for the log-depth reshuffle.

    ``faults_on`` additionally requires the strategy's closed form to be
    exact under the chaos layer's duplicate double-folds and admission
    rejections (``Strategy.fold_affine_supports_faults``): a declining
    strategy (fedbuff) falls back to the sequential scan under "auto" and
    raises under a forced "associative".
    """
    mode = getattr(cfg, "fold_mode", "sequential")
    if mode not in ("sequential", "associative", "auto"):
        raise ValueError(
            f"unknown fold_mode {mode!r}; accepted: "
            "'sequential' | 'associative' | 'auto'")
    if mode == "sequential":
        return None
    if strategy.build_fold(model, cfg_model, cfg) is None:
        return None  # no server fold at all: nothing to parallelize
    if faults_on and not getattr(strategy, "fold_affine_supports_faults",
                                 True):
        if mode == "associative":
            raise ValueError(
                f"fold_mode='associative' with fault injection, but "
                f"strategy {strategy.name!r} declares its affine fold form "
                "inexact under duplicate/rejected arrivals "
                "(fold_affine_supports_faults=False) — use "
                "fold_mode='sequential' or 'auto'")
        return None
    affine = strategy.build_fold_affine(model, cfg_model, cfg)
    if affine is None:
        if mode == "associative":
            raise ValueError(
                f"fold_mode='associative' but strategy {strategy.name!r} "
                "declines the affine fold form (build_fold_affine returned "
                "None) — use fold_mode='sequential' or 'auto', or drop the "
                "non-affine piece (asofed: feature_learning=False)")
        return None
    if mode == "auto" and jax.default_backend() == "cpu":
        return None
    return affine


def tick_body(strategy, model, cfg_model, cfg, mesh: Optional[Mesh], codec,
              slots: Tuple[str, ...], server_slots: Tuple[str, ...] = (),
              faults_on: bool = False):
    """The traceable one-tick update ``(stacked, server, *inputs) ->
    (stacked, server, tel_row)`` — jitted standalone for sync/sweep
    schedules, scanned over a window axis by the async megastep.

    ``slots`` are the strategy's per-client telemetry names;
    ``server_slots`` the post-fold server scalars.  The emitted row is
    ``slots + ("folds_per_tick",) + server_slots`` — the engine-owned
    fold-depth slot (the quantity the associative fold path speeds up)
    always rides in the middle; chaos runs append the per-tick
    ``rejected`` / ``clipped`` admission counters after it.

    The tick always takes the full 12-array input block (the chaos
    columns ``fresh`` / ``dup`` / ``corrupt`` / ``stal`` ride at the
    end); ``faults_on`` and the ``cfg`` guard knobs gate which chaos ops
    are actually traced, so a fault-free, guard-free config compiles the
    exact pre-chaos computation and replays bitwise.

    Index duality: ``idx`` is the *global* client id — it keys server
    arrays (asofed's per-client ``n``), upload-codec PRNG streams, and
    corruption noise, so it must be identical under every state
    residency.  ``lidx`` is the *storage row* of the same client in the
    ``stacked`` carry: equal to ``idx`` under device residency (the
    stack is ``[K+1, ...]``), the window-local pool-block row under host
    residency (the stack is the gathered ``[R, ...]`` cohort block).
    Only the gather and the scatter write-back consume ``lidx`` — the
    arithmetic between them never sees storage coordinates, which is
    what makes the two residencies bitwise-identical.
    """
    local = strategy.build_local(model, cfg)
    fold = strategy.build_fold(model, cfg_model, cfg)
    affine = resolve_fold_affine(strategy, model, cfg_model, cfg,
                                 faults_on=faults_on)
    merge = strategy.build_merge(model, cfg)
    finalize = strategy.build_finalize(model, cfg)
    server_tel = (strategy.build_server_telemetry(model, cfg)
                  if server_slots else None)
    # lazy: the strategy modules import Strategy from repro.sim.engine,
    # so a top-level repro.core import from the sim side would be circular
    from repro.core.algorithms.common import (corrupt_wire_delta,
                                              corruption_key,
                                              resolve_upload_codec)
    from repro.common.pytree import tree_any_nan, tree_l2_norm

    ucodec = resolve_upload_codec(cfg)
    uview = strategy.upload_codec_view(model, cfg)
    guards = (getattr(cfg, "max_staleness", None) is not None
              or getattr(cfg, "max_delta_norm", None) is not None)
    # chaos = fault-aware tick: graceful degradation needs a fold to
    # guard and a wire-delta view to inspect (sweep baselines have
    # neither and stay untouched by construction)
    chaos = ((faults_on or guards) and fold is not None
             and uview is not None)
    if ucodec.identity and not chaos:
        uview = None
    if not ucodec.identity and uview is None:
        # the engine fail-fasts this before compiling; repeated here so
        # tick_body can't silently no-op if reached through another door
        raise ValueError(
            f"upload_codec={ucodec.name!r} requires an upload_codec_view "
            f"from strategy {strategy.name!r}")
    init_one = strategy.build_init_client(model, cfg) if faults_on else None
    # crash-restart rebuilds rows against the run's reference init — the
    # same w0 every oracle derives from the seed (baked constant; the
    # tick cache re-keys on the seed when faults_on)
    w0_init = model.init(jax.random.PRNGKey(cfg.seed)) if faults_on else None
    vlocal = jax.vmap(local, in_axes=(0, None, 0, 0, 0, 0, 0))

    def tick(stacked, server, idx, lidx, xs, ys, delays, n_vis, t_arr, mask,
             fresh, dup, corrupt, stal):
        enc0 = tree_take(stacked, lidx)
        # the stacked state may be delta-compressed: reconstruct the
        # cohort's working (master-dtype) state right at the gather —
        # identity (and fused away) for the fp32 codec
        cohort0 = enc0 if codec is None else codec.decode(enc0)
        if faults_on and init_one is not None:
            # crash-restart: a rejoining client's first round starts from
            # freshly initialized local state (the device lost everything;
            # n_vis is its stream's visible count at rejoin time)
            init_rows = jax.vmap(init_one, in_axes=(None, 0))(w0_init, n_vis)
            cohort0 = mask_select(fresh & mask, init_rows, cohort0)
        bcast = strategy.server_broadcast(server)
        with jax.named_scope("local_rounds"):
            # the vmapped local rounds are embarrassingly parallel over the
            # cohort axis: on a mesh, run them as explicit SPMD shards (the
            # compile-time bucket makes divisibility a trace-time
            # property; non-divisible small buckets fall back to the
            # single-program path)
            if mesh is not None and idx.shape[0] % mesh.devices.size == 0:
                sharded_local = shard_map(
                    vlocal, mesh=mesh,
                    in_specs=(P("data"), P(), P("data"), P("data"), P("data"),
                              P("data"), P("data")),
                    out_specs=(P("data"), P("data"), P("data")),
                    check_vma=False,
                )
                cohort, uploads, tel = sharded_local(
                    cohort0, bcast, xs, ys, delays, n_vis, t_arr)
                if fold is not None:
                    # one explicit all-gather here, so the sequential fold
                    # scan below runs replicated with no per-step collectives
                    rep = sharding_lib.replicated(mesh)
                    uploads = jax.lax.with_sharding_constraint(
                        uploads, jax.tree.map(lambda _: rep, uploads))
            else:
                cohort, uploads, tel = vlocal(
                    cohort0, bcast, xs, ys, delays, n_vis, t_arr)
        if uview is not None and (not ucodec.identity or faults_on):
            # lossy upload compression: round-trip each arrival's wire
            # delta through the UploadCodec before the fold consumes it.
            # The PRNG key (random_mask only) is a pure function of (run
            # seed, arrival stamp, client row) — the per-arrival oracle
            # derives the identical key, so engine == oracle stays exact.
            # Masked padding slots encode garbage that mask_select /
            # tree_where discard, same as the local rounds themselves.
            # Payload corruption (the chaos layer) lands AFTER the codec
            # round-trip: it is a wire fault, so the server sees the
            # corrupted reconstruction — corruption noise is keyed on
            # (seed, t, cid), again oracle-derivable.
            extract, rebuild = uview

            def encode_one(up, c0, t_i, ix, cr):
                d = extract(up, c0, bcast)
                if not ucodec.identity:
                    key = jax.random.fold_in(jax.random.fold_in(
                        jax.random.PRNGKey(cfg.seed), t_i.astype(jnp.int32)),
                        ix.astype(jnp.int32))
                    d = ucodec.encode(d, key)
                if faults_on:
                    d = corrupt_wire_delta(
                        d, cr, corruption_key(cfg.seed, t_i, ix))
                up2 = rebuild(up, d, c0, bcast)
                if ucodec.identity:
                    # identity codec: clean arrivals must stay bitwise
                    # (the extract/rebuild round-trip may reassociate fp)
                    return tree_where(cr > 0, up2, up)
                return up2

            with jax.named_scope("upload_codec"):
                uploads = jax.vmap(encode_one)(uploads, cohort0, t_arr, idx,
                                               corrupt)
        if chaos:
            # server-side graceful degradation, expressed as fold masks +
            # per-slot scales so every fold path (sequential scan, affine
            # prefix) and every per-arrival oracle agree exactly:
            # * non-finite wire deltas are always rejected;
            # * `max_staleness` rejects (or, under "downweight", rescales
            #   by max_staleness/staleness) over-stale arrivals;
            # * `max_delta_norm` clips admitted deltas to that global L2.
            # Rejected/rescaled slots are rebuilt with sanitized deltas
            # (zeros / scaled) so no NaN ever reaches fold arithmetic;
            # admitted unscaled uploads pass through bitwise.
            extract, rebuild = uview
            ms = getattr(cfg, "max_staleness", None)
            mdn = getattr(cfg, "max_delta_norm", None)
            downweight = getattr(cfg, "staleness_policy",
                                 "reject") == "downweight"

            def guard_one(up, c0, st):
                d = extract(up, c0, bcast)
                ok = ~tree_any_nan(d)
                sc = jnp.ones((), jnp.float32)
                if ms is not None:
                    over = st > ms
                    if downweight:
                        sc = sc * jnp.where(
                            over, ms / jnp.maximum(st, 1e-9), 1.0)
                    else:
                        ok = ok & ~over
                if mdn is not None:
                    nrm = tree_l2_norm(d)
                    sc = sc * jnp.where(
                        nrm > mdn, mdn / jnp.maximum(nrm, 1e-30), 1.0)
                return ok, sc

            def adjust_one(up, c0, ok, sc):
                d = extract(up, c0, bcast)
                d2 = jax.tree.map(
                    lambda x: jnp.where(ok, x * sc, jnp.zeros_like(x)), d)
                up2 = rebuild(up, d2, c0, bcast)
                return tree_where(ok & (sc >= 1.0), up, up2)

            ok_s, sc_s = jax.vmap(guard_one)(uploads, cohort0, stal)
            admit = mask & ok_s
            clipped = admit & (sc_s < 1.0)
            uploads = jax.vmap(adjust_one)(uploads, cohort0, ok_s, sc_s)
        else:
            admit = mask
        tel_row = reduce_telemetry(tel, mask, slots)
        if fold is not None:
            with jax.named_scope("fold"):
                # a one-slot bucket has no prefix to parallelize: it runs the
                # sequential step itself, so fold depth 1 is bitwise in every
                # mode (the affine form's final add reaches the backend with
                # its operands swapped, and XLA:CPU contracts the first
                # product into an FMA: 1 ulp apart)
                if affine is not None and idx.shape[0] > 1:
                    # parallel fast path: the tick's folds as one log-depth
                    # affine prefix scan over the coefficient stream (masked
                    # AND rejected slots are identity by the coeffs contract
                    # — `admit` simply joins the mask)
                    carrier, coeffs, unfold = affine
                    a_s, b_s, aux = coeffs(server, uploads, idx, n_vis, t_arr,
                                           admit)
                    if faults_on:
                        # duplicate delivery folds the same upload twice:
                        # composing the slot's affine map with itself gives
                        # a' = a², b' = a·b + b — exact for every strategy
                        # with fold_affine_supports_faults (resolve_fold_
                        # affine already rejected the others)
                        dd = admit & dup
                        b2 = jax.tree.map(
                            lambda b: a_s.reshape(
                                a_s.shape + (1,) * (b.ndim - 1)) * b + b, b_s)
                        b_s = mask_select(dd, b2, b_s)
                        a_s = jnp.where(dd, a_s * a_s, a_s)
                    def prefix(a, b, h0):
                        return scan_ops.fold_prefix(
                            a, b, h0, use_kernel=cfg.fold_kernel,
                            interpret=cfg.fold_kernel_interpret)

                    if mesh is not None:
                        # the fold runs replicated; a Mosaic kernel cannot be
                        # partitioned by the compiler, so every device runs
                        # it whole inside an explicit SPMD region
                        prefix = shard_map(prefix, mesh=mesh,
                                           in_specs=(P(), P(), P()),
                                           out_specs=P(), check_vma=False)
                    h = prefix(a_s, b_s, carrier(server))
                    server, received = unfold(server, h, aux, uploads, idx,
                                              n_vis, t_arr, admit)
                else:
                    def step(sv, inp):
                        up, ix, nv, ta, mk, dp = inp
                        sv2, received = fold(sv, up, ix, nv, ta)
                        if faults_on:
                            # duplicate delivery: fold the same upload again;
                            # the client downloads the post-second-fold model
                            sv3, received2 = fold(sv2, up, ix, nv, ta)
                            sv2 = tree_where(mk & dp, sv3, sv2)
                            received = tree_where(mk & dp, received2, received)
                        # padded/rejected slots leave the server untouched
                        return tree_where(mk, sv2, sv), received
                    server, received = jax.lax.scan(
                        step, server, (uploads, idx, n_vis, t_arr, admit, dup)
                    )
                if chaos:
                    # a rejected client keeps its post-round local state but
                    # receives no download (its fold never happened)
                    cohort = mask_select(
                        admit, jax.vmap(merge)(cohort, received), cohort)
                else:
                    cohort = jax.vmap(merge)(cohort, received)
        if finalize is not None:
            server = finalize(server)
        # engine-owned fold-depth slot + post-fold server scalars
        extras = [jnp.sum(mask.astype(jnp.float32))]
        if server_tel is not None:
            sv_tel = server_tel(server)
            extras += [jnp.asarray(sv_tel[s], jnp.float32)
                       for s in server_slots]
        if chaos:
            # per-tick admission counters (the engine totals them into
            # stats["rejected_uploads"] / ["clipped_uploads"])
            extras += [jnp.sum((mask & ~admit).astype(jnp.float32)),
                       jnp.sum(clipped.astype(jnp.float32))]
        tel_row = jnp.concatenate([tel_row, jnp.stack(extras)])
        # masked write-back: padded slots target the scratch row and revert
        # to their pre-tick (still-encoded) values, so real rows are
        # written exactly once
        with jax.named_scope("write_back"):
            enc = cohort if codec is None else codec.encode(cohort)
            stacked = tree_scatter(stacked, lidx,
                                   mask_select(mask, enc, enc0))
        return stacked, server, tel_row

    return tick


# donate the carried state so XLA reuses its buffers for the outputs
# (the per-tick/window input arrays can't alias either output shape, so
# donating them would only produce unusable-donation warnings); no-op on
# CPU, where donation is unsupported
def _donate():
    return (0, 1) if jax.default_backend() != "cpu" else ()


def build_tick_fn(strategy, model, cfg_model, cfg, mesh: Optional[Mesh],
                  codec=None, slots: Tuple[str, ...] = (),
                  server_slots: Tuple[str, ...] = (),
                  faults_on: bool = False):
    return jax.jit(
        tick_body(strategy, model, cfg_model, cfg, mesh, codec, slots,
                  server_slots, faults_on=faults_on),
        donate_argnums=_donate())


def build_megastep_fn(strategy, model, cfg_model, cfg, mesh: Optional[Mesh],
                      codec=None, slots: Tuple[str, ...] = (),
                      server_slots: Tuple[str, ...] = (),
                      faults_on: bool = False):
    """One fused dispatch per window: ``lax.scan`` of the tick body over
    the leading ``[T_w]`` axis of the staged window block.  Tick ``j+1``'s
    gather reads the rows tick ``j`` scattered (the scan carry), so a
    client arriving twice in one window sees the mid-window server folds
    exactly as it would across two separate dispatches — fully-masked
    padding ticks leave both carries untouched.  The scan's stacked ys
    are the ``[T_w, n_slots]`` telemetry block: one row per fused tick,
    returned by the same dispatch that executes the window."""
    tick = tick_body(strategy, model, cfg_model, cfg, mesh, codec, slots,
                     server_slots, faults_on=faults_on)

    def megastep(stacked, server, idx, lidx, xs, ys, delays, n_vis, t_arr,
                 mask, fresh, dup, corrupt, stal):
        def step(carry, inp):
            stacked_, server_, tel_row = tick(*carry, *inp)
            return (stacked_, server_), tel_row

        (stacked, server), tel = jax.lax.scan(
            step, (stacked, server),
            (idx, lidx, xs, ys, delays, n_vis, t_arr, mask, fresh, dup,
             corrupt, stal)
        )
        return stacked, server, tel

    return jax.jit(megastep, donate_argnums=_donate())


def _cache_get(cache, key, anchors):
    hit = cache.get(key)
    if hit is not None and all(r() is a for r, a in zip(hit[0], anchors)):
        return hit[1]
    return None


def _cache_put(cache, key, anchors, value):
    if len(cache) > 64:  # unbounded model churn guard
        cache.clear()
    cache[key] = (tuple(weakref.ref(a) for a in anchors), value)


def cfg_cache_key(cfg) -> Tuple:
    """Runtime-only fields don't affect the traced computation: normalize
    them out so e.g. benchmark sweeps over T (or prefetch/window/eval
    toggles) reuse one compilation.  ``state_dtype`` stays in the key —
    the codec changes the traced encode/decode ops — and so does ``task``
    (the loss selector); ``workload`` only picks host-side metric bundles.
    """
    return dataclasses.astuple(dataclasses.replace(
        cfg, T=0, sim_time_budget=None, eval_every=0, seed=0,
        max_cohort=None, prefetch=None, window=1, workload=None,
        eval_align=False,
    ))


def tick_fn(strategy, model, cfg_model, cfg, K: int, mesh: Optional[Mesh], *,
            windowed: bool = False, codec=None,
            slots: Tuple[str, ...] = (),
            server_slots: Tuple[str, ...] = (),
            faults_on: bool = False):
    # key by device ids, not just mesh shape: the compiled fn closes over
    # the concrete Mesh, and two same-shape meshes over different devices
    # must not share it.  A non-identity codec additionally closes over
    # its anchor w0 = model.init(PRNGKey(cfg.seed)) — seed-dependent, so
    # the seed (normalized out of the cfg key) must re-enter the key or a
    # second seed's run would decode against the first seed's anchor.
    mesh_key = (tuple(mesh.shape.items()),
                tuple(d.id for d in mesh.devices.flat)) \
        if mesh is not None else None
    # ... and a random_mask upload codec closes over PRNGKey(cfg.seed)
    # the same way (the mask key constant is baked into the trace)
    from repro.core.algorithms.common import resolve_upload_codec

    # ... and a fault-aware tick bakes in w0 = model.init(PRNGKey(seed))
    # (the crash-restart reference init) plus seed-keyed corruption noise
    codec_key = cfg.seed if ((codec is not None and not codec.identity)
                             or resolve_upload_codec(cfg).uses_rng
                             or faults_on) else None
    key = (id(model), id(cfg_model), type(strategy).__name__, strategy.name,
           cfg_cache_key(cfg), K, mesh_key, windowed, codec_key, slots,
           server_slots, faults_on)
    fn = _cache_get(_TICK_CACHE, key, (model, cfg_model))
    if fn is None:
        build = build_megastep_fn if windowed else build_tick_fn
        fn = build(strategy, model, cfg_model, cfg, mesh, codec, slots,
                   server_slots, faults_on=faults_on)
        _cache_put(_TICK_CACHE, key, (model, cfg_model), fn)
    return fn


def batched_init_fn(strategy, model, cfg):
    """Cached ``jit(vmap(init_one))`` for the stacked-state fast init, or
    None when the strategy only provides the per-client path."""
    init_one = strategy.build_init_client(model, cfg)
    if init_one is None:
        return None
    key = (id(model), type(strategy).__name__, strategy.name,
           cfg_cache_key(cfg))
    fn = _cache_get(_INIT_CACHE, key, (model,))
    if fn is None:
        fn = jax.jit(jax.vmap(init_one, in_axes=(None, 0)))
        _cache_put(_INIT_CACHE, key, (model,), fn)
    return fn


def predict_fn(model, per_client: bool):
    key = (id(model), per_client)
    fn = _cache_get(_PREDICT_CACHE, key, (model,))
    if fn is None:
        # the scope decorates the lambda in place: the program stays
        # jit__lambda, the name the benchmark's eval metric reads
        one = jax.named_scope("eval_predict")(
            lambda p, x: model.predict(p, {"x": x}))
        fn = jax.jit(jax.vmap(one, in_axes=(0, 0) if per_client else (None, 0)))
        _cache_put(_PREDICT_CACHE, key, (model,), fn)
    return fn
