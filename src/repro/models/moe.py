"""Mixture-of-Experts with expert parallelism.

Two interchangeable implementations (numerically equivalent up to capacity
drops; tested against each other):

* ``dense``   — single-device reference: every expert runs on every token's
  top-k assignments via gather/scatter.  Used by smoke tests and as oracle.
* ``ep_psum`` — production path: ``shard_map`` over the whole mesh.  Experts
  shard over the ``model`` axis (optionally FSDP over ``data`` on d_model
  rows, all-gathered per layer).  Each model column routes the full local
  token block, capacity-buckets the assignments owned by *its* experts,
  runs the batched expert GEMMs, scatter-adds its partial output and
  ``psum``s over the model axis.  Collectives: 1 psum of (T, d) per MoE
  layer (+ FSDP weight all-gather) — cheaper than a2a dispatch for k >= 4
  (napkin math in EXPERIMENTS.md §Perf).

Both are capacity-dropping (tokens above ``ceil(T*k*cf/E)`` per expert are
dropped, paper-standard); FLOPs are the *active-parameter* count, so
roofline numbers reflect real MoE arithmetic intensity.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.dist import DistContext
from repro.models.spec import ParamDef


def moe_spec(cfg: ModelConfig):
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff_expert
    return {
        "router": ParamDef((d, E), (None, None), init="fan_in"),
        "w_gate": ParamDef((E, d, f), ("experts", "fsdp", "expert_ff"),
                           init="fan_in"),
        "w_up": ParamDef((E, d, f), ("experts", "fsdp", "expert_ff"),
                         init="fan_in"),
        "w_down": ParamDef((E, f, d), ("experts", "expert_ff", "fsdp"),
                           init="fan_in"),
    }


def _route(router_w, xt, k: int):
    """xt: (T, d) -> (gates (T,k) f32, ids (T,k) i32, aux load-balance loss)."""
    logits = (xt @ router_w).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    # switch-style load-balance aux: E * sum_e f_e * p_e
    E = logits.shape[-1]
    me = jnp.mean(probs, axis=0)  # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32), axis=1), axis=0
    )  # fraction routed per expert
    aux = E * jnp.sum(me * ce)
    return gates, ids, aux


def _expert_ffn(buf, wg, wu, wd):
    """buf: (E?, C, d) -> (E?, C, d) batched SwiGLU expert GEMMs."""
    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(buf.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, wd)


def _capacity(T: int, k: int, E: int, cf: float) -> int:
    return max(1, math.ceil(T * k * cf / E))


# ---------------------------------------------------------------------------
# Reference / single-device path
# ---------------------------------------------------------------------------


def moe_dense(params, x, cfg: ModelConfig):
    """Capacity-free (dropless) reference path: exact top-k combine."""
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    T = xt.shape[0]
    k = cfg.top_k
    gates, ids, aux = _route(params["router"], xt, k)
    y = jnp.zeros((T, d), jnp.float32)
    E = cfg.n_experts
    # loop over experts (smoke scale: E <= 4 in tests; fine up to dozens)
    for e in range(E):
        mask = jnp.sum(jnp.where(ids == e, gates, 0.0), axis=-1)  # (T,)
        ye = _expert_ffn(
            xt[None], params["w_gate"][e : e + 1], params["w_up"][e : e + 1],
            params["w_down"][e : e + 1],
        )[0]
        y = y + mask[:, None] * ye.astype(jnp.float32)
    return y.astype(x.dtype).reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Expert-parallel shard_map path
# ---------------------------------------------------------------------------


def _ep_block(xt, router_w, wg, wu, wd, *, cfg: ModelConfig, n_cols: int,
              fsdp_axes, model_axis: str):
    """Per-device block. xt: (T, d) local tokens (replicated over model cols);
    wg/wu/wd: (E_loc, d or d/dd, f) local expert weights."""
    E, k, cf = cfg.n_experts, cfg.top_k, cfg.capacity_factor
    E_loc = E // n_cols
    T, d = xt.shape
    C = _capacity(T, k, E, cf)  # per-expert capacity over the local T tokens
    j = jax.lax.axis_index(model_axis)

    if fsdp_axes:
        wg = jax.lax.all_gather(wg, fsdp_axes, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, fsdp_axes, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, fsdp_axes, axis=2, tiled=True)

    gates, ids, aux = _route(router_w, xt, k)  # (T,k)
    eid = ids.reshape(-1)  # (T*k,)
    tid = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    col = eid // E_loc
    mine = col == j
    le = jnp.where(mine, eid % E_loc, E_loc)  # sentinel E_loc for foreign
    order = jnp.argsort(le, stable=True)
    le_s = le[order]
    tid_s = tid[order]
    starts = jnp.searchsorted(le_s, jnp.arange(E_loc, dtype=le_s.dtype))
    rank = jnp.arange(T * k, dtype=jnp.int32) - starts[
        jnp.clip(le_s, 0, E_loc - 1)
    ].astype(jnp.int32)
    valid = (le_s < E_loc) & (rank < C)
    slot = jnp.where(valid, le_s.astype(jnp.int32) * C + rank, E_loc * C)

    # inverse map: which token fills each buffer slot (ints only — no (Tk,d))
    token_for_slot = jnp.zeros((E_loc * C + 1,), jnp.int32).at[slot].set(tid_s)
    slot_used = jnp.zeros((E_loc * C + 1,), jnp.bool_).at[slot].set(valid)
    buf = xt[token_for_slot[:-1]] * slot_used[:-1, None].astype(xt.dtype)
    buf = buf.reshape(E_loc, C, d)

    yb = _expert_ffn(buf, wg, wu, wd).reshape(E_loc * C, d)
    yb = jnp.concatenate([yb, jnp.zeros((1, d), yb.dtype)], axis=0)

    # combine: per assignment, gather its slot output weighted by its gate
    slot_unsorted = (
        jnp.full((T * k,), E_loc * C, jnp.int32).at[order].set(slot)
    ).reshape(T, k)
    gmask = gates.astype(jnp.float32)

    def acc_k(i, y):
        slot_i = jax.lax.dynamic_index_in_dim(slot_unsorted, i, 1, keepdims=False)
        g_i = jax.lax.dynamic_index_in_dim(gmask, i, 1, keepdims=True)
        contrib = yb[slot_i].astype(jnp.float32)
        return y + g_i * contrib

    y = jax.lax.fori_loop(0, k, acc_k, jnp.zeros((T, d), jnp.float32))
    y = jax.lax.psum(y.astype(xt.dtype), model_axis)
    return y, aux


def moe_ep_psum(params, x, cfg: ModelConfig, dist: DistContext):
    """Expert-parallel MoE over the mesh (see module docstring)."""
    mesh = dist.mesh
    model_axis = dist.model_axis
    n_cols = dist.model_axis_size
    B, S, d = x.shape
    data_axes = dist.data_axes
    # expert-weight specs: experts over model, FSDP rows per the rule table
    # (may span ("pod","data") under fsdp-pod — must match the param layout
    # or SPMD re-gathers the whole expert tree before the shard_map)
    fsdp_res = dist.rules.resolve_axis("fsdp", mesh) if dist.fsdp else None
    if fsdp_res is None:
        fsdp_axes = ()
    elif isinstance(fsdp_res, str):
        fsdp_axes = (fsdp_res,)
    else:
        fsdp_axes = tuple(fsdp_res)
    fsdp_spec = fsdp_axes if len(fsdp_axes) > 1 else (
        fsdp_axes[0] if fsdp_axes else None
    )
    w_row = P("model", fsdp_spec, None)
    w_down_spec = P("model", None, fsdp_spec)
    # batch sharding over data axes, dropping axes B can't divide (B=1 decode)
    ax = tuple(data_axes)
    while ax:
        n = 1
        for a in ax:
            n *= mesh.shape[a]
        if B % n == 0:
            break
        ax = ax[:-1]
    x_spec = P(ax if ax else None, None, None)

    block = partial(
        _ep_block, cfg=cfg, n_cols=n_cols, fsdp_axes=fsdp_axes,
        model_axis=model_axis,
    )

    all_axes = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)

    def mapped(x_, rw, wg, wu, wd):
        xt = x_.reshape(-1, d)
        y, aux = block(xt, rw, wg, wu, wd)
        aux = jax.lax.pmean(aux, all_axes)  # replicate: aux differs per shard
        return y.reshape(x_.shape), aux

    y, aux = shard_map(
        mapped,
        mesh=mesh,
        in_specs=(x_spec, P(None, None), w_row, w_row, w_down_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])
    return y, aux


# ---------------------------------------------------------------------------
# Serving expert parallelism: weights RESIDENT, sharded (experts x d_ff) over
# (data x model); tokens routed to expert owners with all_to_all over data.
# No per-step FSDP weight gathers — the decode-path fix for 1T MoE serving
# (§Perf: the training layout re-gathers ~params bytes per token step).
# ---------------------------------------------------------------------------


def _bucket(ids, n_buckets: int, cap: int):
    """Assignment bucketing: ids (A,) in [0, n_buckets) ->
    (slot (A,) — this assignment's bucket slot, n_buckets*cap if dropped;
     assign_for_slot (n_buckets*cap,) — which assignment fills each slot;
     used (n_buckets*cap,) bool)."""
    A = ids.shape[0]
    order = jnp.argsort(ids, stable=True)
    ids_s = ids[order]
    starts = jnp.searchsorted(ids_s, jnp.arange(n_buckets, dtype=ids_s.dtype))
    rank = jnp.arange(A, dtype=jnp.int32) - starts[
        jnp.clip(ids_s, 0, n_buckets - 1)
    ].astype(jnp.int32)
    valid = (ids_s < n_buckets) & (rank < cap)
    slot_sorted = jnp.where(valid, ids_s.astype(jnp.int32) * cap + rank,
                            n_buckets * cap)
    assign_for_slot = (
        jnp.zeros((n_buckets * cap + 1,), jnp.int32)
        .at[slot_sorted].set(order.astype(jnp.int32))
    )
    used = (
        jnp.zeros((n_buckets * cap + 1,), jnp.bool_).at[slot_sorted].set(valid)
    )
    slot_unsorted = (
        jnp.full((A,), n_buckets * cap, jnp.int32).at[order].set(slot_sorted)
    )
    return slot_unsorted, assign_for_slot[:-1], used[:-1]


def _ep_serve_block(xt, router_w, wg, wu, wd, *, cfg: ModelConfig,
                    n_rows: int, n_cols: int, data_axes, model_axis):
    """Per-device block. xt: (T, d) local tokens (batch over data rows,
    replicated over model cols); wg/wu: (E_loc, d, f_loc); wd: (E_loc, f_loc, d)
    — experts over data rows, d_ff over model cols, fully resident."""
    E, k, cf = cfg.n_experts, cfg.top_k, cfg.capacity_factor
    E_loc = E // n_rows
    T, d = xt.shape
    gates, ids, aux = _route(router_w, xt, k)
    eid = ids.reshape(-1)
    tid = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    dest = eid // E_loc  # owning data row
    # 1) bucket assignments by destination row and all_to_all tokens + ids
    C1 = max(1, math.ceil(T * k * cf / n_rows))
    slot1, asg1, used1 = _bucket(dest, n_rows, C1)
    send_x = (
        xt[tid[asg1]] * used1[:, None].astype(xt.dtype)
    ).reshape(n_rows, C1, d)
    send_le = jnp.where(used1, (eid % E_loc)[asg1], E_loc).reshape(n_rows, C1)
    recv_x = jax.lax.all_to_all(send_x, data_axes, 0, 0, tiled=False)
    recv_le = jax.lax.all_to_all(send_le, data_axes, 0, 0, tiled=False)
    # 2) bucket received tokens by local expert, batched GEMM (f_loc shard)
    R = n_rows * C1
    rx = recv_x.reshape(R, d)
    rle = recv_le.reshape(R)
    C2 = max(1, math.ceil(R * cf / E_loc))
    slot2, asg2, used2 = _bucket(rle, E_loc, C2)
    buf = (rx[asg2] * used2[:, None].astype(rx.dtype)).reshape(E_loc, C2, d)
    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(buf.dtype) * u
    yb = jnp.einsum("ecf,efd->ecd", h, wd)  # partial over f_loc
    yb = jax.lax.psum(yb, model_axis)  # combine d_ff shards
    # 3) un-bucket back to received order, reverse all_to_all, combine
    yb_flat = jnp.concatenate(
        [yb.reshape(E_loc * C2, d), jnp.zeros((1, d), yb.dtype)], axis=0
    )
    y_recv = yb_flat[slot2].reshape(n_rows, C1, d)
    y_send = jax.lax.all_to_all(y_recv, data_axes, 0, 0, tiled=False)
    y_flat = jnp.concatenate(
        [y_send.reshape(n_rows * C1, d), jnp.zeros((1, d), y_send.dtype)], 0
    )
    contrib = y_flat[slot1]  # (T*k, d) rows in assignment order
    y = jnp.zeros((T, d), jnp.float32).at[tid].add(
        gates.reshape(-1)[:, None] * contrib.astype(jnp.float32)
    )
    return y.astype(xt.dtype), aux


def moe_ep_serve(params, x, cfg: ModelConfig, dist: DistContext):
    mesh = dist.mesh
    n_rows = 1
    for a in dist.data_axes:
        n_rows *= mesh.shape[a]
    n_cols = dist.model_axis_size
    B, S, d = x.shape
    data_axes = dist.data_axes if len(dist.data_axes) > 1 else dist.data_axes[0]
    w_spec = P(dist.data_axes if len(dist.data_axes) > 1 else dist.data_axes[0],
               None, "model")
    wd_spec = P(dist.data_axes if len(dist.data_axes) > 1 else dist.data_axes[0],
                "model", None)
    ax = tuple(dist.data_axes)
    while ax:
        n = 1
        for a in ax:
            n *= mesh.shape[a]
        if B % n == 0:
            break
        ax = ax[:-1]
    x_spec = P(ax if ax else None, None, None)
    all_axes = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)

    block = partial(
        _ep_serve_block, cfg=cfg, n_rows=n_rows, n_cols=n_cols,
        data_axes=data_axes, model_axis=dist.model_axis,
    )

    def mapped(x_, rw, wg, wu, wd):
        xt = x_.reshape(-1, d)
        y, aux = block(xt, rw, wg, wu, wd)
        aux = jax.lax.pmean(aux, all_axes)
        return y.reshape(x_.shape), aux

    return shard_map(
        mapped, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, wd_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])


def moe_forward(params, x, cfg: ModelConfig, dist: DistContext):
    impl = dist.resolve_moe_impl()
    if impl == "dense" or dist.mesh is None or dist.model_axis_size == 1:
        return moe_dense(params, x, cfg)
    if impl == "ep_serve":
        n_rows = 1
        for a in dist.data_axes:
            n_rows *= dist.mesh.shape[a]
        if cfg.n_experts % n_rows or cfg.d_ff_expert % dist.model_axis_size:
            raise ValueError("ep_serve needs experts % data == 0 and "
                             "d_ff_expert % model == 0")
        return moe_ep_serve(params, x, cfg, dist)
    if cfg.n_experts % dist.model_axis_size:
        raise ValueError(
            f"{cfg.n_experts} experts not divisible by model axis "
            f"({dist.model_axis_size})"
        )
    return moe_ep_psum(params, x, cfg, dist)
