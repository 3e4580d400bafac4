"""Compiles of the main path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described and not attached, and refuses what the chip would refuse
(tiling-rule violations, ops Mosaic cannot lower, VMEM overruns) — the
faults interpret mode cannot see.  Each test asserts that the compiled
program really holds the kernel (``tpu_custom_call``), not a fallback.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
all import every test file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.common import sharding as sharding_lib
from repro.core.algorithms import get_strategy
from repro.kernels.feature_attention.ops import feature_attention
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.linear_scan.ops import fold_prefix, linear_scan
from repro.sim import compile as compile_lib
from repro.sim.engine import run_strategy
from repro.sim.workloads import get_workload


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text):
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows,cols", [(4096, 1024), (225, 256)])
def test_feature_attention_compiles(one_chip, rows, cols):
    text = _compile_text(lambda w: feature_attention(w, use_kernel=True),
                         ((rows, cols), jnp.float32), sharding=one_chip)
    _assert_kernel(text)


@pytest.mark.parametrize("shape", [(1, 32, 16384), (2, 1024, 256)])
def test_linear_scan_compiles(one_chip, shape):
    text = _compile_text(lambda a, b: linear_scan(a, b, use_kernel=True),
                         (shape, jnp.float32), (shape, jnp.float32),
                         sharding=one_chip)
    _assert_kernel(text)


@pytest.mark.parametrize("S", [1, 32])
def test_fold_prefix_kernel_compiles(one_chip, S):
    """The associative server fold over a paper-width 64x256 leaf."""
    text = _compile_text(
        lambda a, b, h0: fold_prefix(a, b, h0, use_kernel=True),
        ((S,), jnp.float32), ((S, 64, 256), jnp.float32),
        ((64, 256), jnp.float32), sharding=one_chip)
    _assert_kernel(text)


def test_flash_attention_compiles(one_chip):
    B, S, KV, G, hd = 2, 1024, 2, 2, 128

    def fn(q, k, v, pos):
        return flash_attention(q, k, v, q_positions=pos, k_positions=pos)

    text = _compile_text(
        fn, ((B, S, KV, G, hd), jnp.bfloat16), ((B, S, KV, hd), jnp.bfloat16),
        ((B, S, KV, hd), jnp.bfloat16), ((B, S), jnp.int32),
        sharding=one_chip)
    _assert_kernel(text)


class _Captured(Exception):
    pass


def test_sharded_associative_megastep_compiles(topo, monkeypatch):
    """fedasync's window program with the associative fold on the
    linear_scan kernel, sharded over a described four-chip ``data`` mesh
    as ``mesh="auto"`` places it: the compiler cannot partition a Mosaic
    kernel, so the replicated fold must reach it inside an SPMD region.
    The argument shapes come from a small run on the CPU (15 clients: the
    16-row client stack divides the mesh, as the engine pads it there)."""
    wl = get_workload("lstm_regression")
    cfg_model, model = wl.build(hidden=64)
    cfg = wl.run_config(T=64, eval_every=0, window=4, seed=0,
                        fold_mode="associative", fold_kernel=True,
                        fold_kernel_interpret=True)
    seen = {}

    def capture(*a, **kw):
        def record(*args):
            seen.update(kw=kw, args=args)
            raise _Captured

        return record

    monkeypatch.setattr(compile_lib, "tick_fn", capture)
    with pytest.raises(_Captured):
        run_strategy(get_strategy("fedasync"), model, cfg_model,
                     wl.make_clients(15, n_per=40), cfg, mesh=None)
    mesh = Mesh(np.array(topo.devices), ("data",),
                axis_types=(AxisType.Auto,))
    kw = seen["kw"]
    fn = compile_lib.build_megastep_fn(
        get_strategy("fedasync"), model, cfg_model,
        dataclasses.replace(cfg, fold_kernel_interpret=False), mesh,
        kw["codec"], kw["slots"], kw["server_slots"],
        faults_on=kw["faults_on"])
    stacked, server, *window = seen["args"]

    def place(x, sharding):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    args = (
        jax.tree.map(lambda x: place(
            x, sharding_lib.client_sharding(x.shape, mesh)), stacked),
        jax.tree.map(lambda x: place(
            x, sharding_lib.replicated(mesh)), server),
        *[place(x, sharding_lib.window_sharding(x.shape, mesh))
          for x in window],
    )
    # the client stack and the local rounds shard too
    assert jax.tree.leaves(stacked)[0].shape[0] % 4 == 0
    assert window[0].shape[1] % 4 == 0
    _assert_kernel(jax.jit(fn).lower(*args).compile().as_text())
