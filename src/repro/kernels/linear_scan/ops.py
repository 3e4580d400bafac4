"""Public wrapper for the linear-scan kernel.

Accepts the model-side layouts:
  Mamba : a, b (B, S, d_inner, N) — flattened to C = d_inner * N
  RG-LRU: a, b (B, S, width)

``use_kernel=None`` auto-selects the lowering the same way
``feature_attention`` does: the Pallas kernel on TPU once the stream is
large enough to be HBM-bandwidth-bound, the sequential jnp reference
below that (and always off-TPU, where the kernel would run interpreted).

:func:`fold_prefix` adapts the same kernel to the cohort engine's
*server-fold* stream: one tick's per-arrival affine coefficients map onto
the kernel's flattened layout with B=1, S=folds-per-tick, C=param-leaf
size (reusing :func:`_pick_chunk`), so the sequential Eq. (4)-style fold
recurrence runs at log depth.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.linear_scan.kernel import linear_scan_kernel
from repro.kernels.linear_scan.ref import linear_scan_ref

_VMEM_TILE_BYTES = 4 * 1024 * 1024

# Auto-dispatch threshold in elements (fp32), mirroring
# feature_attention.ops: below ~1 MB the stream is cache/VMEM-resident
# and the pallas_call launch overhead dominates; above it the fused
# chunked scan wins on TPU.
KERNEL_MIN_ELEMS = 1 << 18


def _pick_chunk(S: int, C: int) -> int:
    chunk = max(8, _VMEM_TILE_BYTES // max(8 * C, 1))
    chunk = min(256, chunk, S)
    # power-of-two for the doubling scan
    p = 1
    while p * 2 <= chunk:
        p *= 2
    return p


def use_kernel_default(n_elems: int) -> bool:
    """The ``use_kernel=None`` auto rule (trace-time: shapes are static)."""
    return jax.default_backend() == "tpu" and n_elems >= KERNEL_MIN_ELEMS


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def linear_scan(a, b, *, use_kernel: Optional[bool] = None,
                interpret: bool = False):
    """Returns (h, h_last) in the input layout.

    ``use_kernel``: True forces the Pallas kernel, False the sequential
    reference, None picks by backend and size (``use_kernel_default``).
    """
    shape = a.shape
    B, S = shape[0], shape[1]
    a2 = a.reshape(B, S, -1)
    b2 = b.reshape(B, S, -1)
    C = a2.shape[-1]
    if use_kernel is None:
        use_kernel = use_kernel_default(a2.size)
    if use_kernel:
        h, hlast = linear_scan_kernel(
            a2, b2, chunk=_pick_chunk(S, C), interpret=interpret
        )
    else:
        h, hlast = linear_scan_ref(a2, b2)
    return h.reshape(shape), hlast.reshape((B,) + shape[2:])


def _rows(v, ndim: int):
    """(S,) coefficient broadcast against an (S, ...) leaf."""
    return v.reshape(v.shape + (1,) * (ndim - 1))


def fold_prefix(a, b, h0=None, *, use_kernel: Optional[bool] = None,
                interpret: bool = False):
    """Inclusive prefix states of an affine fold stream, at log depth.

    ``a``: (S,) per-arrival decay coefficients; ``b``: pytree of
    ``(S, ...)`` leaves; ``h0``: pytree matching ``b`` without the leading
    axis (None = zeros).  Returns the pytree ``h`` of ``(S, ...)`` states
    with ``h_s = a_s * h_{s-1} + b_s`` seeded at ``h0`` — the result the
    sequential fold scan would produce, up to fp reassociation and the
    backend's choice of fused multiply-adds.

    Internally ``h_s = A_s * h0 + B_s`` with ``A = cumprod(a)`` and ``B``
    the zero-seeded prefix: per-leaf, large streams ride the Pallas
    kernel as a (1, S, C) flattened block (``use_kernel`` True forces it,
    None auto-picks via ``use_kernel_default``), the rest share one
    ``jax.lax.associative_scan``.  Everything is fp32.
    """
    a32 = a.astype(jnp.float32)
    S = a32.shape[0]
    A = jnp.cumprod(a32)
    leaves, treedef = jax.tree.flatten(b)
    flags = [use_kernel if use_kernel is not None
             else use_kernel_default(x.size) for x in leaves]
    out = [None] * len(leaves)
    for i, (x, f) in enumerate(zip(leaves, flags)):
        if not f:
            continue
        C = max(1, x.size // S)
        x2 = x.reshape(1, S, C).astype(jnp.float32)
        a2 = jnp.broadcast_to(a32[None, :, None], (1, S, C))
        h, _ = linear_scan_kernel(a2, x2, chunk=_pick_chunk(S, C),
                                  interpret=interpret)
        out[i] = h[0].reshape(x.shape)
    rest = [i for i, f in enumerate(flags) if not f]
    if rest:
        def combine(lo, hi):
            la, lb = lo
            ha, hb = hi
            return (la * ha,
                    tuple(_rows(ha, x.ndim) * x + y for x, y in zip(lb, hb)))

        _, Bs = jax.lax.associative_scan(
            combine,
            (a32, tuple(leaves[i].astype(jnp.float32) for i in rest)),
        )
        for i, Bl in zip(rest, Bs):
            out[i] = Bl
    if h0 is not None:
        out = [_rows(A, Bl.ndim) * x[None] + Bl
               for Bl, x in zip(out, jax.tree.leaves(h0))]
    return treedef.unflatten(out)
