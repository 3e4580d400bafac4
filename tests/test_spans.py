"""The engine's ``repro.*`` program spans on the profiler's clock.

One tiny asynchronous FedAsync run (prefetch on, host-resident state, a
checkpoint, evals) is traced with ``jax.profiler.trace``; its spans are read
back from the ``.xplane.pb`` the profiler writes.  The run is the only
profiler session of this file: under xdist's ``--dist loadfile`` one file's
tests share a worker process, and one process holds one profiler session at
a time.
"""
import dataclasses
import gc
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.algorithms import get_strategy
from repro.data import airquality_like
from repro.models import LOCAL, build_model
from repro.sim import compile as compile_lib
from repro.sim.engine import RunConfig, run_strategy
from repro.sim.profiles import make_sim_clients
from repro.sim.telemetry import FullCollections, span

# every span the engine opens on this run's path, with the thread it is on
DISPATCHING = ("repro.run", "repro.run.setup", "repro.setup.init_state",
               "repro.setup.tick_fn", "repro.setup.evaluator",
               "repro.window.wait", "repro.dispatch", "repro.dispatch.sync",
               "repro.telemetry.append", "repro.pool.patch",
               "repro.pool.scatter", "repro.eval.predict",
               "repro.eval.extract", "repro.checkpoint.save",
               "repro.telemetry.finalize", "repro.builder.close")
PRODUCER = ("repro.sched.peek_window", "repro.build_window",
            "repro.pool.gather")
PARENT = {  # child -> the span it nests in
    "repro.run.setup": "repro.run",
    "repro.setup.init_state": "repro.run.setup",
    "repro.setup.tick_fn": "repro.run.setup",
    "repro.setup.evaluator": "repro.run.setup",
    "repro.window.wait": "repro.run",
    "repro.dispatch": "repro.run",
    "repro.dispatch.sync": "repro.dispatch",
    "repro.telemetry.append": "repro.dispatch",
    "repro.pool.patch": "repro.dispatch",
    "repro.pool.scatter": "repro.dispatch",
    "repro.eval.predict": "repro.run",
    "repro.eval.extract": "repro.run",
    "repro.checkpoint.save": "repro.run",
    "repro.telemetry.finalize": "repro.run",
    "repro.builder.close": "repro.run",
    "repro.pool.gather": "repro.build_window",
}


def _setup():
    data = airquality_like(n_clients=6, n_per=60)
    cfg_model = dataclasses.replace(
        get_arch("paper-lstm"), in_features=8, out_features=1, hidden=8)
    return data, cfg_model, build_model(cfg_model, LOCAL)


CFG = RunConfig(T=48, batch_size=8, local_epochs=1, eta=0.02,
                task="regression", eval_every=16, seed=0, window=4,
                prefetch=True, state_residency="host")


def _run(data, cfg_model, model, ckpt_dir):
    stats, trace = {}, []
    hist = run_strategy(
        get_strategy("fedasync"), model, cfg_model,
        make_sim_clients(data, seed=0), CFG, stats=stats, trace=trace,
        mesh=None, checkpoint_path=os.path.join(ckpt_dir, "ckpt"),
        checkpoint_every=16)
    return hist, stats, trace


def _read_spans(trace_dir):
    """[(name, host line index, start ns, end ns)] of the ``repro.*``
    events in the trace."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.name, (plane.name, i), ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    data, cfg_model, model = _setup()
    plain = _run(data, cfg_model, model, str(tmp_path_factory.mktemp("a")))
    tdir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(tdir):
        run = _run(data, cfg_model, model, str(tmp_path_factory.mktemp("b")))
    return plain, run, _read_spans(tdir)


def test_every_span_is_on_its_thread(traced):
    _, _, spans = traced
    lines = {}
    for name, line, _, _ in spans:
        lines.setdefault(name, set()).add(line)
    missing = set(DISPATCHING + PRODUCER) - set(lines)
    assert not missing, missing
    (run_line,) = lines["repro.run"]
    for name in DISPATCHING:
        assert lines[name] == {run_line}, name
    # prefetch on: the producer's spans are on another host line
    for name in PRODUCER:
        assert run_line not in lines[name], name


def test_children_nest_in_their_parents(traced):
    _, _, spans = traced
    by_line = {}
    for name, line, s, e in spans:
        by_line.setdefault(line, []).append((name, s, e))
    for name, line, s, e in spans:
        parent = PARENT.get(name)
        if parent is None:
            continue
        assert any(p == parent and ps <= s and e <= pe
                   for p, ps, pe in by_line[line]), (name, s, e)


def test_stats_are_sums_of_their_spans(traced):
    _, (_, stats, _), spans = traced

    def total(name):
        return sum(e - s for n, _, s, e in spans if n == name) / 1e9

    for key, name in (("host_build_s", "repro.build_window"),
                      ("device_s", "repro.dispatch"),
                      ("window_wait_s", "repro.window.wait"),
                      ("run_setup_s", "repro.run.setup")):
        assert stats[key] == pytest.approx(total(name), rel=0.05), key
    assert stats["gc_full_collections"] >= 0


def test_trajectory_is_bitwise_with_the_profiler_on(traced):
    (hist_a, stats_a, trace_a), (hist_b, stats_b, trace_b), _ = traced
    assert [h.metrics for h in hist_a] == [h.metrics for h in hist_b]
    assert [t for t, _ in trace_a] == [t for t, _ in trace_b]
    for (_, pa), (_, pb) in zip(trace_a, trace_b):
        for x, y in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
            np.testing.assert_array_equal(x, y)
    assert stats_a["iters"] == stats_b["iters"] == CFG.T


def test_span_feeds_its_accumulator_and_nothing_else():
    class Clock:
        seconds = 0.0

    clock = Clock()
    with span("repro.test", clock, "seconds", bucket=4):
        pass
    first = clock.seconds
    assert first > 0
    with span("repro.test"):
        pass
    assert clock.seconds == first


def test_full_collections_counts_generation_two_only():
    before = list(gc.callbacks)
    with FullCollections() as full:
        gc.collect(0)
        gc.collect(1)
        gc.collect(2)
        gc.collect()
    assert full.count == 2
    assert gc.callbacks == before


def test_megastep_carries_its_named_scopes(monkeypatch):
    """The compiled megastep names its phases in each op's metadata: an
    ASO-Fed window (feature pass on) with a lossy upload codec has all
    five, and the eval's predict names its own."""
    data, cfg_model, model = _setup()
    cfg = dataclasses.replace(CFG, T=12, state_residency="device",
                              upload_codec="topk_sparse", window=2)
    calls = []
    real = compile_lib.tick_fn

    def spy(*args, **kwargs):
        fn = real(*args, **kwargs)

        def call(*inputs):
            calls.append((fn, inputs))
            return fn(*inputs)

        return call

    monkeypatch.setattr(compile_lib, "tick_fn", spy)
    clients = make_sim_clients(data, seed=0)
    run_strategy(get_strategy("asofed"), model, cfg_model, clients, cfg,
                 mesh=None)
    fn, inputs = calls[0]
    text = fn.lower(*inputs).compile().as_text()
    for scope in ("local_rounds", "upload_codec", "fold", "feature_pass",
                  "write_back"):
        assert f"/{scope}/" in text, scope
    predict = compile_lib.predict_fn(model, False)
    assert predict.__name__ == "<lambda>"  # the program stays jit__lambda
    params = model.init(jax.random.PRNGKey(0))
    x = np.stack([c.test_x for c in clients])
    assert "eval_predict" in predict.lower(params, x).compile().as_text()
