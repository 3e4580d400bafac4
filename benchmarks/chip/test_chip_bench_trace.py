"""The trace reduction on a small trace recorded on a TPU v5e
(``record_fixture.py``: one FedAsync CNN job, 8 clients), and its interval
arithmetic on hand-made cases."""
import json
import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import peaks  # noqa: E402
import trace_reduce  # noqa: E402

FIXTURE = HERE / "fixtures" / "cnn_fedasync_small.xplane.pb"


def test_union_merges_overlaps_and_clips():
    iv = [(5, 9), (0, 3), (2, 4), (8, 12), (20, 30)]
    assert trace_reduce.union(iv, 1, 25) == [(1, 4), (5, 12), (20, 25)]
    busy = trace_reduce.union(iv, 0, 40)
    assert trace_reduce.gaps_of(busy, 0, 40) == [(4, 5), (12, 20), (30, 40)]


def test_names():
    assert trace_reduce.base_name("jit_megastep(7336640877008729379)") == \
        "jit_megastep"
    assert trace_reduce.op_name("%while.242 = (f32[2]) while(...)") == \
        "%while.242"


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce_file(str(FIXTURE), span="bench.job")


def test_fixture_window_and_busy(summary):
    assert summary.n_devices == 1
    assert 0 < summary.busy_s < summary.window_s < 5.0
    idle = sum(ns for _, ns in summary.gaps) / 1e9
    assert idle == pytest.approx(summary.window_s - summary.busy_s, rel=1e-9)


def test_fixture_programs(summary):
    # the window's fused megastep holds nearly all device time; the eval's
    # predict and the state init run as jit__lambda programs
    mega = summary.module_s("jit_megastep")
    assert 0.5 * summary.busy_s < mega <= summary.busy_s
    assert summary.module_s("jit__lambda") > 0
    ops = summary.top_ops(10)
    assert len(ops) == 10 and ops[0][1] >= ops[-1][1] > 0


def _reader(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fixture_linear_scan_calls(summary):
    rd = _reader("linear_scan.device_us_per_iter")
    assert summary.kernels and all(rd.is_call(k.text)
                                   for k in summary.kernels)
    spent_ns = sum(k.duration_ns for k in summary.kernels)
    us = rd.read(types.SimpleNamespace(trace=summary, iters=32))
    assert us == pytest.approx(spent_ns / 1e3 / 32)
    assert rd.read(types.SimpleNamespace(trace=summary, iters=0)) is None


def test_linear_scan_call_cost_by_hand():
    # which kernel calls are linear_scan's: by the shapes of their HLO
    # text, whatever the layout
    rd = _reader("linear_scan.device_us_per_iter")
    hbm = ("%c.1 = (f32[1,8,100]{2,1,0:T(8,128)}, f32[1,1,100]{2,1,0}) "
           "custom-call(f32[1,8,100]{2,1,0} %a, f32[1,8,100]{2,1,0} %b), "
           'custom_call_target="tpu_custom_call"')
    assert rd.is_call(hbm)
    on_chip = hbm.replace("{2,1,0:T(8,128)}", "{2,1,0:T(8,128)S(1)}") \
        .replace("{2,1,0} %", "{2,1,0:S(1)} %")
    assert rd.is_call(on_chip)
    assert not rd.is_call(hbm.replace("f32[1,1,100]", "f32[1,2,100]"))
    assert not rd.is_call(hbm.replace("f32[1,8,100]{2,1,0} %b", "%b"))
    nothing = types.SimpleNamespace(
        trace=types.SimpleNamespace(kernels=[]), iters=32)
    assert rd.read(nothing) is None


def test_fixture_per_layer_readers(summary):
    cell = types.SimpleNamespace(
        config=json.loads((HERE / "configs" /
                           "paper-cnn.fmnist.json").read_text()),
        traffic={"run_config": {"local_epochs": 2, "batch_size": 32}})
    ctx = types.SimpleNamespace(
        trace=summary, stats={"host_build_s": 0.01}, iters=32,
        ticks=[(8, 8, 1.0), (16, 8, 1.0)], wall_s=summary.window_s,
        cell=cell, chips=1, peak=peaks.peak("TPU v5 lite"))
    for name in ("device_idle_pct", "eval_pct", "step_mfu_pct"):
        v = _reader(name).read(ctx)
        assert 0 < v < 100, (name, v)
    assert _reader("megastep.device_us_per_iter").read(ctx) > 0
    assert _reader("folds_per_tick").read(ctx) == 8
    assert _reader("host_build_us_per_iter").read(ctx) == \
        pytest.approx(0.01 / 32 * 1e6)
