"""Shared low-level utilities: pytrees, dtypes, sharding rules, registry."""
from repro.common.pytree import (
    tree_add,
    tree_sub,
    tree_scale,
    tree_axpy,
    tree_dot,
    tree_l2_norm,
    tree_zeros_like,
    param_count,
    param_bytes,
    tree_any_nan,
)
from repro.common.registry import Registry

__all__ = [
    "tree_add",
    "tree_sub",
    "tree_scale",
    "tree_axpy",
    "tree_dot",
    "tree_l2_norm",
    "tree_zeros_like",
    "param_count",
    "param_bytes",
    "tree_any_nan",
    "Registry",
]
