"""Roofline-term derivation from the compiled dry-run artifact.

TPU v5e constants (targets; the container is CPU-only so terms are derived,
not measured):
    peak bf16 compute : 197 TFLOP/s per chip
    HBM bandwidth     : 819 GB/s per chip
    ICI link bandwidth: ~50 GB/s per link
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import numpy as np


from repro.configs.base import ModelConfig, ShapeConfig
from repro.models.model import build_spec
from repro.models.spec import is_def

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    model_flops: float
    useful_ratio: float  # MODEL_FLOPS / HLO_FLOPS (global)
    chips: int

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    def as_dict(self) -> Dict:
        d = dataclasses.asdict(self)
        d["dominant"] = self.dominant
        return d


def param_counts(cfg: ModelConfig) -> Dict[str, float]:
    """Total and active (per-token) parameter counts from the spec tree."""
    import jax

    spec = build_spec(cfg)
    total = 0
    routed = 0
    for path, d in jax.tree.flatten_with_path(spec, is_leaf=is_def)[0]:
        n = int(np.prod(d.shape))
        total += n
        keys = [str(getattr(p, "key", "")) for p in path]
        if "moe" in keys and any(k.startswith("w_") for k in keys):
            routed += n
    active = total - routed
    if cfg.n_experts:
        active += routed * (cfg.top_k / cfg.n_experts)
    return {"total": float(total), "active": float(active)}


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """6*N*D for training, 2*N*D for prefill, 2*N*B for one decode step,
    N = active params (MoE uses activated count)."""
    counts = param_counts(cfg)
    n = counts["active"]
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


def derive(analysis: Dict, chips: int, cfg: ModelConfig,
           shape: ShapeConfig) -> RooflineTerms:
    """analysis: the dict from repro.launch.hlo.analyze() — per-device,
    while-trip-count-scaled flops / HBM traffic / collective wire bytes
    (XLA's own cost_analysis counts loop bodies once; see hlo.py)."""
    flops_dev = float(analysis.get("flops", 0.0))
    bytes_dev = float(analysis.get("hbm_bytes", 0.0))
    wire_dev = float(analysis.get("wire_bytes", 0.0))
    mf = model_flops(cfg, shape)
    hlo_flops_global = flops_dev * chips
    return RooflineTerms(
        compute_s=flops_dev / PEAK_FLOPS,
        memory_s=bytes_dev / HBM_BW,
        collective_s=wire_dev / ICI_BW,
        hlo_flops=flops_dev,
        hlo_bytes=bytes_dev,
        collective_bytes=wire_dev,
        model_flops=mf,
        useful_ratio=mf / max(hlo_flops_global, 1.0),
        chips=chips,
    )
