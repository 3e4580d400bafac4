"""Pallas TPU flash-attention kernel (causal / sliding-window / local, GQA).

Grid: (B, H, n_q_blocks, n_kv_blocks) — the KV axis is innermost, so on TPU
it executes sequentially per (b, h, qi) and the online-softmax accumulators
live in VMEM scratch across KV steps.  GQA is expressed in the K/V BlockSpec
index maps (head h reads KV head h // G) — KV tiles are fetched once per
group without materializing the repeat.

Block skipping: with contiguous positions (prefill/train), causal and
sliding-window bounds are static in the program ids, so fully-masked KV
blocks are skipped with ``pl.when`` — the kernel does the O(S*W) work for
SWA instead of the XLA path's O(S^2) (EXPERIMENTS.md §Perf).

VMEM per step: q/k/v tiles (blk_q + 2*blk_k) * hd * 4 B + (blk_q, blk_k)
score tile + accumulators — ~1.3 MB at the default 512/512/hd=128 fp32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(qp_ref, kp_ref, q_ref, k_ref, v_ref, o_ref,
               acc_ref, m_ref, l_ref, *, scale, causal, window, blk_q, blk_k,
               contiguous):
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (blk_q, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (blk_k, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (blk_q, blk_k)
        qp = qp_ref[0]  # (blk_q, 1)
        kp = kp_ref[0]  # (1, blk_k)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= kp <= qp
        if window > 0:
            mask &= (qp - kp) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...][:, 0]  # (blk_q,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[...][:, 0] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new[:, None]
        l_ref[...] = l_new[:, None]

    if contiguous:
        # static bounds in block indices: positions == arange
        qi = pl.program_id(2)
        q_lo = qi * blk_q
        q_hi = q_lo + blk_q - 1
        k_lo = ki * blk_k
        needed = jnp.bool_(True)
        if causal:
            needed &= k_lo <= q_hi
        if window > 0:
            k_hi = k_lo + blk_k - 1
            needed &= k_hi > q_lo - window

        @pl.when(needed)
        def _run():
            compute()
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[...][:, 0]
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]
        ).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, q_positions, k_positions, *, causal,
                           window, scale=None, blk_q=512, blk_k=512,
                           contiguous=False, interpret=False):
    """q: (B, H, Sq, hd); k/v: (B, KV, Skv, hd); positions: (B, S*) int32."""
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    G = H // KV
    blk_q = min(blk_q, Sq)
    while Sq % blk_q:
        blk_q //= 2
    blk_k = min(blk_k, Skv)
    while Skv % blk_k:
        blk_k //= 2
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    grid = (B, H, Sq // blk_q, Skv // blk_k)
    kern = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        blk_q=blk_q, blk_k=blk_k, contiguous=contiguous,
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            # positions as (B, Sq, 1) and (B, 1, Skv): a block's last two
            # dims are then whole or tiled, as the (8, 128) rule asks
            pl.BlockSpec((1, blk_q, 1), lambda b, h, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, 1, blk_k), lambda b, h, qi, ki: (b, 0, ki)),
            pl.BlockSpec((1, 1, blk_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec(
                (1, 1, blk_k, hd), lambda b, h, qi, ki: (b, h // G, ki, 0)
            ),
            pl.BlockSpec(
                (1, 1, blk_k, hd), lambda b, h, qi, ki: (b, h // G, ki, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, blk_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, hd), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q_positions[:, :, None], k_positions[:, None, :], q, k, v)
