"""Sharding-aware checkpointing.

Flat-key npz payload + a JSON manifest (tree structure, dtypes, logical
axes).  On restore under a mesh, arrays are placed with jax.device_put
against the rule-resolved shardings — each host would read only its shard
in a real multi-host deployment (single-process here; the API is the same).
"""
from __future__ import annotations

import json
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np


def _flatten(tree):
    flat, treedef = jax.tree.flatten_with_path(tree)
    keys = ["/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
            for path, _ in flat]
    vals = [v for _, v in flat]
    return keys, vals, treedef


def save_checkpoint(path: str, params, step: int = 0, extra: Optional[dict] = None):
    os.makedirs(path, exist_ok=True)
    keys, vals, _ = _flatten(params)
    arrays = {f"arr_{i}": np.asarray(v) for i, v in enumerate(vals)}
    np.savez(os.path.join(path, "params.npz"), **arrays)
    manifest = {
        "step": step,
        "keys": keys,
        "dtypes": [str(np.asarray(v).dtype) for v in vals],
        "extra": extra or {},
    }
    with open(os.path.join(path, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=2)


def _named_dtype(name: str) -> np.dtype:
    """np.dtype from a manifest name, resolving ml_dtypes extension types
    (bfloat16 etc.) that plain ``np.dtype(str)`` does not know."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def load_checkpoint(path: str, like, shardings=None):
    """Restore into the structure of ``like`` (a params pytree or spec)."""
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    data = np.load(os.path.join(path, "params.npz"))
    keys, _, treedef = _flatten(like)
    saved_keys = manifest["keys"]
    if keys != saved_keys:
        # a symmetric set-diff is empty when the two key lists hold the
        # same names in a different order — report each side explicitly
        missing = [k for k in saved_keys if k not in keys]
        unexpected = [k for k in keys if k not in saved_keys]
        if missing or unexpected:
            raise ValueError(
                f"checkpoint structure mismatch at {path!r}: saved keys "
                f"not in target {missing}; target keys not in checkpoint "
                f"{unexpected}")
        raise ValueError(
            f"checkpoint structure mismatch at {path!r}: same keys, "
            f"different order (saved {saved_keys}, target {keys})")
    if len(data.files) != len(saved_keys):
        raise ValueError(
            f"corrupt checkpoint at {path!r}: manifest lists "
            f"{len(saved_keys)} arrays but params.npz holds "
            f"{len(data.files)}")
    vals = [data[f"arr_{i}"] for i in range(len(keys))]
    # .npy round-trips extension dtypes (ml_dtypes bfloat16: the
    # delta-compressed client-state codec) as raw void bytes; the manifest
    # records the true dtype — view the bits back, exactly
    vals = [v if str(v.dtype) == dt else v.view(_named_dtype(dt))
            for v, dt in zip(vals, manifest["dtypes"])]
    if shardings is not None:
        sh_leaves = jax.tree.leaves(shardings)
        vals = [jax.device_put(v, s) for v, s in zip(vals, sh_leaves)]
    else:
        vals = [jnp.asarray(v) for v in vals]
    return jax.tree.unflatten(treedef, vals), manifest["step"]
