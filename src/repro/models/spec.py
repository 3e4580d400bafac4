"""Parameter-spec machinery.

Model code declares parameters once, as a pytree of ``ParamDef`` leaves
(shape + logical axes + initializer).  From that single tree we derive:

* ``init_params``      -- materialized params (smoke tests, paper models)
* ``abstract_params``  -- jax.ShapeDtypeStruct stand-ins (dry-run: a 1T-param
                          model is lowered without allocating a byte)
* ``logical_axes``     -- pytree of logical-axis tuples
* ``param_shardings``  -- pytree of NamedSharding under a rules/mesh pair

This is the single-source-of-truth that keeps model code, sharding rules and
the dry-run in sync.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.sharding import ShardingRules


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"  # normal | zeros | ones | fan_in | uniform_scaled
    scale: float = 0.02

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(
                f"shape {self.shape} and logical axes {self.axes} rank mismatch"
            )


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def _init_leaf(d: ParamDef, key, dtype):
    if d.init == "zeros":
        return jnp.zeros(d.shape, dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, dtype)
    if d.init == "normal":
        return (jax.random.normal(key, d.shape, jnp.float32) * d.scale).astype(dtype)
    if d.init == "fan_in":
        fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[-1]
        std = 1.0 / math.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, d.shape, jnp.float32) * std).astype(dtype)
    if d.init == "uniform_scaled":
        lim = d.scale
        return jax.random.uniform(
            key, d.shape, jnp.float32, minval=-lim, maxval=lim
        ).astype(dtype)
    raise ValueError(f"unknown init {d.init!r}")


def init_params(spec, key, dtype=jnp.float32):
    """Materialize a spec tree into concrete parameter arrays."""
    leaves, treedef = jax.tree.flatten(spec, is_leaf=is_def)
    keys = jax.random.split(key, len(leaves))
    vals = [_init_leaf(d, k, dtype) for d, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_params(spec, dtype=jnp.bfloat16, rules=None, mesh=None):
    """ShapeDtypeStruct tree (optionally with shardings) -- zero allocation."""

    def leaf(d: ParamDef):
        if rules is not None and mesh is not None:
            return jax.ShapeDtypeStruct(
                d.shape, dtype, sharding=rules.sharding(d.axes, mesh)
            )
        return jax.ShapeDtypeStruct(d.shape, dtype)

    return jax.tree.map(leaf, spec, is_leaf=is_def)


def logical_axes(spec):
    return jax.tree.map(lambda d: d.axes, spec, is_leaf=is_def)


def param_shardings(spec, rules: ShardingRules, mesh):
    return jax.tree.map(
        lambda d: rules.sharding(d.axes, mesh), spec, is_leaf=is_def
    )


def param_pspecs(spec, rules: ShardingRules, mesh):
    return jax.tree.map(lambda d: rules.pspec(d.axes, mesh), spec, is_leaf=is_def)


def spec_param_count(spec) -> int:
    return sum(
        int(np.prod(d.shape)) for d in jax.tree.leaves(spec, is_leaf=is_def)
    )


def validate_divisibility(spec, rules: ShardingRules, mesh) -> None:
    """Raise early if any parameter can't be laid out on the mesh."""
    for path, d in jax.tree.flatten_with_path(spec, is_leaf=is_def)[0]:
        try:
            rules.check_divisible(d.shape, d.axes, mesh)
        except ValueError as e:
            raise ValueError(f"at param {jax.tree_util.keystr(path)}: {e}") from e
