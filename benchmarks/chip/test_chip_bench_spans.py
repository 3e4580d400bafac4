"""The program's ``repro.*`` spans in a trace (``program_trace.py``) and the
per-layer metrics that read them, on hand-made cases, on the small trace
recorded before the program had spans (``record_fixture.py``) and on one
recorded with them (``record_span_fixture.py``: the same 8-client job)."""
import importlib.util
import json
import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import peaks  # noqa: E402
import program_trace  # noqa: E402
import trace_reduce  # noqa: E402

OLD = HERE / "fixtures" / "cnn_fedasync_small.xplane.pb"
NEW = HERE / "fixtures" / "cnn_fedasync_spans.xplane.pb"


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ctx(summary, stats):
    cell = types.SimpleNamespace(
        config=json.loads((HERE / "configs" /
                           "paper-cnn.fmnist.json").read_text()),
        traffic={"run_config": {"local_epochs": 2, "batch_size": 32}})
    return types.SimpleNamespace(
        trace=summary, stats=stats, iters=32,
        ticks=[(8, 8, 1.0), (16, 8, 1.0)], wall_s=summary.window_s,
        cell=cell, chips=1, peak=peaks.peak("TPU v5 lite"))


# ---------------------------------------------------------------------------
# hand-made cases
# ---------------------------------------------------------------------------


def test_span_readers_by_hand():
    wait = _reader("window_wait_us_per_iter")
    setup = _reader("run_setup_ms")
    ctx = types.SimpleNamespace(
        stats={"window_wait_s": 0.004, "run_setup_s": 0.125}, iters=32)
    assert wait.read(ctx) == pytest.approx(0.004 / 32 * 1e6)
    assert setup.read(ctx) == pytest.approx(125.0)
    # a program without the spans (the parent) has no such stats
    bare = types.SimpleNamespace(stats={"host_build_s": 0.01}, iters=32)
    assert wait.read(bare) is None and setup.read(bare) is None
    assert wait.read(types.SimpleNamespace(
        stats={"window_wait_s": 0.0}, iters=0)) is None


def test_labels_and_unattributed_share_by_hand():
    run = ("repro.run", "h:0", 0, 100)
    wait = ("repro.window.wait", "h:0", 10, 30)
    build = ("repro.build_window", "h:0", 12, 20)
    assert program_trace.label_at(15, [wait, build]) == "repro.build_window"
    assert program_trace.label_at(25, [wait, build]) == "repro.window.wait"
    assert program_trace.label_at(50, [wait, build]) == "unattributed"
    pt = program_trace.ProgramTrace(
        spans=[run, wait, build],
        gaps=[("repro.window.wait", 10, 40), ("unattributed", 50, 60),
              ("repro.window.wait", 60, 120)])
    assert pt.idle_unattributed_pct == pytest.approx(10.0)
    assert pt.idle_by_span() == pytest.approx(
        {"repro.window.wait": 90e-9, "unattributed": 10e-9})
    assert pt.top_gaps(1) == [["repro.window.wait", 60e-9]]
    assert program_trace.ProgramTrace([], []).idle_unattributed_pct is None


# ---------------------------------------------------------------------------
# the trace recorded before the program had spans
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def old_summary():
    return trace_reduce.reduce_file(str(OLD), span="bench.job")


def test_old_fixture_has_no_program_spans(old_summary):
    pt = program_trace.read_file(str(OLD))
    assert pt.spans == [] and pt.gaps == []
    assert pt.idle_unattributed_pct is None
    ctx = _ctx(old_summary, {"host_build_s": 0.01})
    assert _reader("window_wait_us_per_iter").read(ctx) is None
    assert _reader("run_setup_ms").read(ctx) is None


def test_old_fixture_readings_unchanged(old_summary):
    # the accepted readers' values on this trace, to the last digit
    ctx = _ctx(old_summary, {"host_build_s": 0.01})
    expected = {
        "device_idle_pct": 76.35697757731108,
        "eval_pct": 2.212454706336337,
        "step_mfu_pct": 0.5466457357854868,
        "megastep.device_us_per_iter": 572.7816875000001,
        "linear_scan.device_us_per_iter": 0.67896875,
        "folds_per_tick": 8.0,
        "host_build_us_per_iter": 312.5,
    }
    for name, value in expected.items():
        assert _reader(name).read(ctx) == pytest.approx(value, rel=1e-12), \
            name


# ---------------------------------------------------------------------------
# the trace recorded with the program's spans
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def new_trace():
    return (trace_reduce.reduce_file(str(NEW), span="bench.job"),
            program_trace.read_file(str(NEW)))


def test_new_fixture_spans_and_threads(new_trace):
    _, pt = new_trace
    (run_line,) = pt.lines_of("repro.run")
    for name in ("repro.run.setup", "repro.window.wait", "repro.dispatch",
                 "repro.dispatch.sync", "repro.eval.predict",
                 "repro.eval.extract"):
        assert pt.lines_of(name) == {run_line}, name
    # the window builds run on the prefetch thread
    assert pt.lines_of("repro.build_window")
    assert run_line not in pt.lines_of("repro.build_window")


def test_new_fixture_gaps_cover_the_idle_time_of_the_run(new_trace):
    summary, pt = new_trace
    ((_, _, lo, hi),) = [sp for sp in pt.spans if sp[0] == "repro.run"]
    assert summary.window[0] <= lo < hi <= summary.window[1]
    idle_in_run = sum(e - s for _, s, e in pt.gaps)
    assert 0 < idle_in_run < hi - lo
    # the run's idle time, by the benchmark's own reduction over its span
    run_only = trace_reduce.reduce_file(str(NEW), span="bench.run")
    assert idle_in_run <= (run_only.window_s - run_only.busy_s) * 1e9 + 1
    assert all(lo <= s < e <= hi for _, s, e in pt.gaps)
    assert all(label.startswith("repro.") or label == "unattributed"
               for label, _, _ in pt.gaps)
    assert "repro.run" not in {label for label, _, _ in pt.gaps}
    assert 0 <= pt.idle_unattributed_pct <= 100


def test_new_fixture_keeps_the_accepted_readings(new_trace):
    # program spans change nothing the accepted metrics read: the gaps
    # keep the benchmark's labels, every reader still reads
    summary, _ = new_trace
    assert {label for label, _ in summary.gaps} <= {
        "bench.job", "bench.job_setup", "bench.run", "bench.extract",
        "host"}
    ctx = _ctx(summary, {"host_build_s": 0.01})
    for name in ("device_idle_pct", "eval_pct", "step_mfu_pct"):
        assert 0 < _reader(name).read(ctx) < 100, name
    assert _reader("megastep.device_us_per_iter").read(ctx) > 0
    assert _reader("linear_scan.device_us_per_iter").read(ctx) > 0
