"""The paper's LSTM (ASO-Fed sec. 5.3): one LSTM layer of ``hidden``
units over sequences of ``in_features`` features, and a linear head on
the last hidden state.

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
    F, H, O = model["in_features"], model["hidden"], model["out_features"]
    return {"w_x": ((F, 4 * H), "fan_in"), "w_h": ((H, 4 * H), "fan_in"),
            "b": ((4 * H,), "zeros"), "fc_w": ((H, O), "fan_in"),
            "fc_b": ((O,), "zeros")}


def first_layer(model: dict) -> str:
    return "w_x"


def forward(model: dict, p, x):
    zx = jnp.einsum("btf,fg->btg", x, p["w_x"]) + p["b"]

    def cell(carry, zt):
        h, c = carry
        z = zt + h @ p["w_h"]
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), None

    h0 = jnp.zeros((x.shape[0], p["w_h"].shape[0]), x.dtype)
    (h, _), _ = jax.lax.scan(cell, (h0, h0), jnp.swapaxes(zx, 0, 1))
    return h @ p["fc_w"] + p["fc_b"]


def loss(model: dict, p, x, y):
    return tasks.loss(model["task"], forward(model, p, x), y)


def seq_len(config: dict) -> int:
    return int(config["dataset"].get("T", 0))


def forward_flops_per_sample(model: dict, seq_len: int = 0) -> int:
    """Multiply-adds x 2 of one sequence's forward pass."""
    O, H, F = model["out_features"], model["hidden"], model["in_features"]
    # input and recurrent projections into the four gates, every step,
    # then the head on the last hidden state
    return 2 * seq_len * (F * 4 * H + H * 4 * H) + 2 * H * O


def train_flops_per_iter(config: dict, traffic: dict) -> int:
    """Forward + backward (3x forward) over one local round's samples."""
    rc = traffic["run_config"]
    samples = rc["local_epochs"] * rc["batch_size"]
    return 3 * samples * forward_flops_per_sample(config["model"],
                                                  seq_len(config))


def program_overrides(model: dict) -> dict:
    """The fields of the program's ``ModelConfig`` the configuration sets."""
    return {k: model[k] for k in ("in_features", "out_features", "hidden")}


def pred_block(model: dict) -> int:
    return 4096  # test sequences predicted in one call
