"""Operations from shapes: the model FLOPs of one global iteration.

A global iteration is one client's local round: forward and backward
(twice the forward) over ``local_epochs * batch_size`` samples.  The fold,
the feature pass and padded slots are not counted.  Each family module
(``families/<model.family>.py``) counts its own model; these dispatch.
"""
from __future__ import annotations

from reference import family


def forward_flops_per_sample(model: dict, seq_len: int = 0) -> int:
    """Multiply-adds x 2 of one sample's forward pass."""
    return family(model).forward_flops_per_sample(model, seq_len)


def seq_len(config: dict) -> int:
    return family(config["model"]).seq_len(config)


def train_flops_per_iter(config: dict, traffic: dict) -> int:
    """Forward + backward (3x forward) over one local round's samples."""
    return family(config["model"]).train_flops_per_iter(config, traffic)
