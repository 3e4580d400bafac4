"""Server-side global feature-representation learning (paper §4.1, Eq. 5-6).

After each aggregation the server rescales the *first layer after the input*
by a row-softmax attention over the weight magnitudes:

    alpha[i, j] = exp(|w1[i, j]|) / sum_j exp(|w1[i, j]|)
    w1[i, j]   <- alpha[i, j] * w1[i, j]

The hot path is the Pallas kernel (repro.kernels.feature_attention); the
model-specific first-layer parameter path is resolved here.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax

from repro.configs.base import ModelConfig
from repro.kernels.feature_attention.ops import feature_attention


def first_layer_path(cfg: ModelConfig) -> Tuple[str, ...]:
    """Path (nested dict keys) of the feature-learning target parameter."""
    if cfg.family == "lstm":
        return ("w_x",)
    if cfg.family == "cnn":
        return ("conv1_w",)
    # transformer families: the token embedding is the first layer after
    # the input (DESIGN.md §2 — hardware-adaptation note)
    return ("embed", "table")


def _get(tree, path: Sequence[str]):
    for k in path:
        tree = tree[k]
    return tree


def _set(tree, path: Sequence[str], value):
    if len(path) == 1:
        out = dict(tree)
        out[path[0]] = value
        return out
    out = dict(tree)
    out[path[0]] = _set(tree[path[0]], path[1:], value)
    return out


def apply_feature_learning(params, cfg: ModelConfig, *,
                           use_kernel: Optional[bool] = False,
                           interpret: bool = False):
    """Returns params with the Eq.(5)-(6) pass applied to the first layer.

    ``use_kernel`` follows :func:`feature_attention`: True/False force the
    Pallas/jnp lowering, None auto-selects by backend and first-layer size.
    The default stays False so oracle paths (``repro.core.server`` and the
    ``sim/reference`` loops) keep the pure-jnp reference lowering.
    """
    path = first_layer_path(cfg)
    w1 = _get(params, path)
    with jax.named_scope("feature_pass"):
        w1 = feature_attention(w1, use_kernel=use_kernel,
                               interpret=interpret)
    return _set(params, path, w1)
