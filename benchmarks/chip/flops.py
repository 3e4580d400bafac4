"""Operations from shapes: the model FLOPs of one global iteration.

A global iteration is one client's local round: forward and backward
(twice the forward) over ``local_epochs * batch_size`` samples.  The fold,
the feature pass and padded slots are not counted.
"""
from __future__ import annotations


def forward_flops_per_sample(model: dict, seq_len: int = 0) -> int:
    """Multiply-adds x 2 of one sample's forward pass."""
    O, H = model["out_features"], model["hidden"]
    if model["family"] == "lstm":
        F = model["in_features"]
        # input and recurrent projections into the four gates, every step,
        # then the head on the last hidden state
        return 2 * seq_len * (F * 4 * H + H * 4 * H) + 2 * H * O
    side = model["image_side"]
    conv1 = 2 * side * side * 9 * 1 * H
    conv2 = 2 * side * side * 9 * H * H
    fc = 2 * (side // 2) * (side // 2) * H * O
    return conv1 + conv2 + fc


def seq_len(config: dict) -> int:
    return int(config["dataset"].get("T", 0)) \
        if config["model"]["family"] == "lstm" else 0


def train_flops_per_iter(config: dict, traffic: dict) -> int:
    """Forward + backward (3x forward) over one local round's samples."""
    rc = traffic["run_config"]
    samples = rc["local_epochs"] * rc["batch_size"]
    return 3 * samples * forward_flops_per_sample(config["model"],
                                                  seq_len(config))
