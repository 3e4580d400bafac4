"""Pallas TPU kernel for the chunked linear recurrence (Mamba-1 / RG-LRU).

h_t = a_t * h_{t-1} + b_t  over the sequence axis, channels vectorized.

Grid: (B, n_chunks) with the chunk axis innermost (sequential on TPU).  The
inter-chunk carry lives in VMEM scratch; within a chunk the recurrence is
solved with a log-depth Hillis-Steele doubling scan on the (chunk, C) tile —
the TPU-idiomatic replacement for the original Mamba CUDA warp scan
(DESIGN.md §2): all work is (8,128)-lane vector ops on VMEM-resident tiles,
no cross-lane shuffles needed.

VMEM per step: 2 * chunk * C * 4 B tiles + carry (1, C); ops.py picks
chunk so this stays < ~4 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(a_ref, b_ref, h_ref, hlast_ref, carry_ref, *, chunk):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    a = a_ref[0].astype(jnp.float32)  # (chunk, C)
    b = b_ref[0].astype(jnp.float32)

    # Hillis-Steele inclusive scan with combine (a1,b1)*(a2,b2) =
    # (a1*a2, a2*b1 + b2); offsets are static so the loop unrolls.
    off = 1
    while off < chunk:
        a_sh = jnp.pad(a, ((off, 0), (0, 0)), constant_values=1.0)[:chunk]
        b_sh = jnp.pad(b, ((off, 0), (0, 0)), constant_values=0.0)[:chunk]
        b = a * b_sh + b
        a = a * a_sh
        off *= 2

    h0 = carry_ref[...]  # (1, C)
    h_all = b + a * h0  # broadcast over chunk rows
    h_ref[0] = h_all.astype(h_ref.dtype)
    # static row slices: an integer index lowers to a dynamic_slice,
    # which Mosaic cannot lower
    carry_ref[...] = h_all[-1:, :]

    @pl.when(ci == nc - 1)
    def _last():
        hlast_ref[0] = h_all[-1:, :].astype(hlast_ref.dtype)


def linear_scan_kernel(a, b, *, chunk: int = 256, interpret: bool = False):
    """a, b: (B, S, C) -> (h (B,S,C), h_last (B,C))."""
    B, S, C = a.shape
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    grid = (B, S // chunk)
    kern = functools.partial(_scan_kernel, chunk=chunk)
    h, hlast = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, C), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, C), lambda bi, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, C), lambda bi, ci: (bi, ci, 0)),
            # (B, 1, C) keeps the block's last two dims whole, as the
            # (8, 128) tiling rule asks for any B
            pl.BlockSpec((1, 1, C), lambda bi, ci: (bi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, C), b.dtype),
            jax.ShapeDtypeStruct((B, 1, C), b.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, C), jnp.float32)],
        interpret=interpret,
    )(a, b)
    return h, hlast[:, 0]
