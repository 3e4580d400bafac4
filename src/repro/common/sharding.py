"""Logical-axis sharding rules engine.

Model code annotates every tensor with *logical* axis names
(e.g. ("layers", "heads", "d_model", "head_dim")).  A rule table maps logical
names to mesh axes.  Resolution is mesh-aware:

* the special logical axis "batch" expands to every data-like mesh axis
  present (("pod", "data") on the multi-pod mesh, ("data",) on one pod), so
  the same rules file drives both meshes;
* a rule whose mesh axis is absent from the mesh resolves to None (replicated)
  -- this is what lets single-device smoke tests reuse production rules;
* divisibility is checked at resolution time so sharding bugs surface as
  errors at lowering, not as silent replication.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]

# Mesh axes that carry data parallelism (in nesting order, outermost first).
DATA_LIKE_AXES: Tuple[str, ...] = ("pod", "data")


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Immutable logical-axis -> mesh-axis rule table."""

    rules: Mapping[str, MeshAxes]
    name: str = "unnamed"

    def resolve_axis(self, logical: Optional[str], mesh: Mesh) -> MeshAxes:
        if logical is None:
            return None
        if logical == "batch":
            present = tuple(a for a in DATA_LIKE_AXES if a in mesh.axis_names)
            return present if present else None
        spec = self.rules.get(logical, None)
        if spec is None:
            return None
        if isinstance(spec, str):
            spec = (spec,)
        expanded = []
        for axis in spec:
            if axis == "batch":  # allow "batch" inside composite rules
                expanded.extend(a for a in DATA_LIKE_AXES if a in mesh.axis_names)
            elif axis in mesh.axis_names:
                expanded.append(axis)
        if not expanded:
            return None
        return tuple(expanded) if len(expanded) > 1 else expanded[0]

    def pspec(self, logical_axes: Sequence[Optional[str]], mesh: Mesh) -> P:
        used: set = set()
        parts = []
        for logical in logical_axes:
            axes = self.resolve_axis(logical, mesh)
            if axes is None:
                parts.append(None)
                continue
            tup = (axes,) if isinstance(axes, str) else axes
            fresh = tuple(a for a in tup if a not in used)
            used.update(fresh)
            if not fresh:
                parts.append(None)
            else:
                parts.append(fresh if len(fresh) > 1 else fresh[0])
        return P(*parts)

    def sharding(
        self, logical_axes: Sequence[Optional[str]], mesh: Mesh
    ) -> NamedSharding:
        return NamedSharding(mesh, self.pspec(logical_axes, mesh))

    def pspec_for_shape(
        self, shape: Sequence[int], logical_axes: Sequence[Optional[str]],
        mesh: Mesh,
    ) -> P:
        """Like pspec, but drops mesh axes a dim cannot divide (e.g. batch=1
        on long-context decode).  Tries prefixes of composite axis tuples so
        e.g. batch=2 on ('pod','data')=32 still shards 2-way over 'pod'."""
        base = self.pspec(logical_axes, mesh)
        parts = []
        for dim, part in zip(shape, tuple(base) + (None,) * len(shape)):
            if part is None:
                parts.append(None)
                continue
            tup = (part,) if isinstance(part, str) else tuple(part)
            while tup:
                n = 1
                for a in tup:
                    n *= mesh.shape[a]
                if dim % n == 0:
                    break
                tup = tup[:-1]
            if not tup:
                parts.append(None)
            else:
                parts.append(tup if len(tup) > 1 else tup[0])
        return P(*parts)

    def sharding_for_shape(
        self, shape: Sequence[int], logical_axes: Sequence[Optional[str]],
        mesh: Mesh,
    ) -> NamedSharding:
        return NamedSharding(mesh, self.pspec_for_shape(shape, logical_axes, mesh))

    def check_divisible(
        self, shape: Sequence[int], logical_axes: Sequence[Optional[str]], mesh: Mesh
    ) -> None:
        spec = self.pspec(logical_axes, mesh)
        for dim, part in zip(shape, spec):
            if part is None:
                continue
            tup = (part,) if isinstance(part, str) else part
            n = 1
            for a in tup:
                n *= mesh.shape[a]
            if dim % n:
                raise ValueError(
                    f"dim {dim} (logical {logical_axes}) not divisible by mesh "
                    f"extent {n} for axes {tup} under rules {self.name!r}"
                )

    def override(self, name: str = "", **updates: MeshAxes) -> "ShardingRules":
        merged = dict(self.rules)
        merged.update(updates)
        return ShardingRules(rules=merged, name=name or f"{self.name}+override")


def _mk(name: str, rules: Dict[str, MeshAxes]) -> ShardingRules:
    return ShardingRules(rules=rules, name=name)


# ---------------------------------------------------------------------------
# Presets
# ---------------------------------------------------------------------------

# Paper-faithful baseline: pure data parallelism. Parameters replicated,
# activations batch-sharded. This mirrors the paper's setting (every client
# holds a full model copy; only data is partitioned).
DP_ONLY = _mk(
    "dp_only",
    {
        "batch": ("pod", "data"),
        # all parameter axes replicated
    },
)

# Megatron-style tensor parallelism over the "model" axis + DP over data axes.
# "act_seq" is the residual-stream sequence axis: sharded over model
# (Megatron sequence parallelism) so per-device activations scale 1/TP;
# attention/MLP internals gather it back as needed ("seq" stays replicated
# under TP).  rules_for(seq_parallel=False) gives the naive baseline.
TP = _mk(
    "tp",
    {
        "batch": ("pod", "data"),
        "act_seq": "model",
        "heads": "model",
        "kv_heads": "model",
        "d_ff": "model",
        "experts": "model",
        "vocab": "model",
        "d_inner": "model",  # SSM expanded channel dim
        # d_model / attention-internal seq replicated
    },
)

# TP + ZeRO-3/FSDP: additionally shard the non-TP parameter axis over data.
TP_FSDP = _mk(
    "tp_fsdp",
    {
        "batch": ("pod", "data"),
        "heads": "model",
        "kv_heads": "model",
        "d_ff": "model",
        "experts": ("data", "model"),  # big MoE: experts over both axes
        "vocab": "model",
        "d_inner": "model",
        "fsdp": "data",  # weight d_model rows sharded over data
    },
)

# Sequence parallelism: weights replicated (optionally FSDP over data),
# activations sharded over `model` on the sequence axis.  Used by archs whose
# head count does not divide the 16-way model axis (whisper 12H, qwen2 14H,
# phi4 24H): attention gathers K/V over `model`, everything else is local.
SEQP = _mk(
    "seqp",
    {
        "batch": ("pod", "data"),
        "seq": "model",
        "act_seq": "model",
        "ce_seq": "model",  # cross-entropy chunk seq axis
        "cache_seq": "model",  # decode: KV cache seq-sharded, LSE-combined
    },
)

# Cohort simulation engine: the stacked per-client state pytree and the
# per-tick cohort arrays carry a leading "clients" axis that is pure data
# parallelism — shard it over every data-like mesh axis, replicate the
# server state and model parameters (each client holds a full copy, as in
# the paper).  Resolution through pspec_for_shape keeps the engine correct
# on any mesh: a bucket or row count the mesh extent cannot divide simply
# replicates.
COHORT = _mk(
    "cohort",
    {
        "batch": ("pod", "data"),
        "clients": ("pod", "data"),
    },
)

# Decode-time rules: KV cache batch over data, heads over model; for B=1
# long-context the sequence axis of the cache shards over data.
DECODE = _mk(
    "decode",
    {
        "batch": ("pod", "data"),
        "heads": "model",
        "kv_heads": "model",
        "d_ff": "model",
        "experts": "model",
        "vocab": "model",
        "d_inner": "model",
        "cache_seq": None,  # overridden to "data" for long-context B=1
    },
)

PRESETS: Dict[str, ShardingRules] = {
    "dp_only": DP_ONLY,
    "tp": TP,
    "tp_fsdp": TP_FSDP,
    "seqp": SEQP,
    "decode": DECODE,
    "cohort": COHORT,
}


def data_mesh(min_devices: int = 2) -> Optional[Mesh]:
    """1-D ``data`` mesh over every local device; None on a single device.

    The cohort engine's auto-mesh: with one device the unsharded code path
    is strictly cheaper than a degenerate mesh, so callers treat None as
    "skip sharding entirely".
    """
    devices = jax.devices()
    if len(devices) < min_devices:
        return None
    # Auto axes: the engine places arrays and lets the compiler propagate
    # shardings (JAX's default, Explicit axes, would demand an
    # out_sharding on every gather from the sharded client stack)
    return jax.make_mesh((len(devices),), ("data",),
                         axis_types=(AxisType.Auto,))


def client_sharding(shape: Sequence[int], mesh: Optional[Mesh],
                    rules: ShardingRules = COHORT) -> Optional[NamedSharding]:
    """Sharding for an array whose axis 0 is the client/cohort axis.

    None when no mesh is active.  Non-divisible leading dims replicate
    (``pspec_for_shape``), so power-of-two tick buckets below the device
    count still execute.
    """
    if mesh is None:
        return None
    axes = ("clients",) + (None,) * (len(shape) - 1)
    return rules.sharding_for_shape(shape, axes, mesh)


def window_sharding(shape: Sequence[int], mesh: Optional[Mesh],
                    rules: ShardingRules = COHORT) -> Optional[NamedSharding]:
    """Sharding for a megastep window block ``[T, bucket, ...]``.

    The leading window axis is a *time* axis (the fused ticks execute
    sequentially inside one ``lax.scan``) so it stays replicated; axis 1
    is the per-tick client/cohort axis and shards over the data mesh
    exactly as :func:`client_sharding` does for a single tick.  The scan
    slices axis 0 away, handing each step a tick whose sharding matches
    the unfused path.  Non-divisible cohort buckets replicate, as in
    :func:`client_sharding`.
    """
    if mesh is None:
        return None
    axes = (None, "clients") + (None,) * (len(shape) - 2)
    return rules.sharding_for_shape(shape, axes, mesh)


def replicated(mesh: Optional[Mesh]) -> Optional[NamedSharding]:
    """Fully-replicated NamedSharding on ``mesh`` (None when no mesh)."""
    if mesh is None:
        return None
    return NamedSharding(mesh, P())


def get_rules(name: str) -> ShardingRules:
    if name not in PRESETS:
        raise KeyError(f"unknown sharding preset {name!r}; known: {sorted(PRESETS)}")
    return PRESETS[name]


def make_sharding_fn(rules: ShardingRules, mesh: Mesh):
    """Returns fn(logical_axes) -> NamedSharding bound to (rules, mesh)."""

    def fn(logical_axes: Sequence[Optional[str]]) -> NamedSharding:
        return rules.sharding(logical_axes, mesh)

    return fn


def tree_pspecs(logical_tree, rules: ShardingRules, mesh: Mesh):
    """Map a pytree of logical-axis tuples to a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda axes: rules.pspec(axes, mesh),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(a, (str, type(None))) for a in x),
    )
