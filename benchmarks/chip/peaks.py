"""Peak rates of each chip the benchmark runs on, keyed by JAX's
``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture
table): 197 TFLOP/s bf16 and 819 GB/s of HBM bandwidth per chip.  The
models run in float32 at JAX's default matmul precision, which the TPU
executes as single-pass bf16 matmuls, so utilization is stated over the
bf16 peak.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud TPU v5e documentation: "
                              "197 TFLOP/s bf16, 819 GB/s HBM"},
}


def peak(device_kind: str) -> dict:
    """The peak table's entry for ``device_kind``; an unknown chip is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
