"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel package has three modules:
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (layout/reshape + flag plumbing)
  ref.py    — pure-jnp oracle used by the allclose test sweeps

Kernels here (hot spots of the ASO-Fed system):
  feature_attention — the paper's Eq.(5)-(6) server-side feature pass
  flash_attention   — blocked online-softmax attention (causal/SWA/local, GQA)
  linear_scan       — chunked linear recurrence (Mamba-1 / RG-LRU)

Numerics are checked against the refs on CPU with interpret=True
(tests/test_kernels.py).  On a TPU the same code compiles to Mosaic:
tests/test_tpu_compile.py compiles each kernel for a described v5e, and
chip_smoke.py runs feature_attention and linear_scan compiled inside the
cohort engine on the chip.
"""
