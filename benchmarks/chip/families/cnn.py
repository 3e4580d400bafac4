"""The paper's CNN (ASO-Fed sec. 5.3): two 3x3 convolutions with ReLU,
a 2x2 max-pool and one fully-connected head, on ``image_side`` square
single-channel images; ``hidden`` channels in both convolutions.

A family module of the chip benchmark: ``reference.family`` loads it by
the configuration's ``model.family``.  It imports nothing of ``repro``.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

import tasks
from tasks import headline, report  # noqa: F401  (the family's eval)


def param_shapes(model: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, init) of the configuration's parameters."""
    C, O = model["hidden"], model["out_features"]
    side = model["image_side"] // 2
    return {"conv1_w": ((3, 3, 1, C), "fan_in"), "conv1_b": ((C,), "zeros"),
            "conv2_w": ((3, 3, C, C), "fan_in"), "conv2_b": ((C,), "zeros"),
            "fc_w": ((side * side * C, O), "fan_in"), "fc_b": ((O,), "zeros")}


def first_layer(model: dict) -> str:
    return "conv1_w"


def forward(model: dict, p, x):
    def conv(v, w, b):
        y = jax.lax.conv_general_dilated(
            v, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jax.nn.relu(y + b)

    v = conv(x, p["conv1_w"], p["conv1_b"])
    v = conv(v, p["conv2_w"], p["conv2_b"])
    v = jax.lax.reduce_window(v, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                              (1, 2, 2, 1), "VALID")
    return v.reshape(v.shape[0], -1) @ p["fc_w"] + p["fc_b"]


def loss(model: dict, p, x, y):
    return tasks.loss(model["task"], forward(model, p, x), y)


def seq_len(config: dict) -> int:
    return 0  # images have no sequence axis


def forward_flops_per_sample(model: dict, seq_len: int = 0) -> int:
    """Multiply-adds x 2 of one image's forward pass."""
    O, H = model["out_features"], model["hidden"]
    side = model["image_side"]
    conv1 = 2 * side * side * 9 * 1 * H
    conv2 = 2 * side * side * 9 * H * H
    fc = 2 * (side // 2) * (side // 2) * H * O
    return conv1 + conv2 + fc


def train_flops_per_iter(config: dict, traffic: dict) -> int:
    """Forward + backward (3x forward) over one local round's samples."""
    rc = traffic["run_config"]
    samples = rc["local_epochs"] * rc["batch_size"]
    return 3 * samples * forward_flops_per_sample(config["model"])


def program_overrides(model: dict) -> dict:
    """The fields of the program's ``ModelConfig`` the configuration sets."""
    return {k: model[k] for k in ("in_features", "out_features", "hidden")}


def pred_block(model: dict) -> int:
    return 4096  # test images predicted in one call
