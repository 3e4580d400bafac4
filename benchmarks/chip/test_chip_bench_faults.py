"""A whole run at CPU-test size, past the harness's look for a chip: sound,
``correct`` comes out true; with the timed path broken underneath, false.

The faults: a step that returns its state unchanged; half of each local
batch left out, the mean taken over the rest; an eval answer altered where
it is produced.  (The cells run on one chip, so no exchange between chips
can be left out.)
"""
import json
import sys
import types
from pathlib import Path

import jax
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import run  # noqa: E402
from small_cells import small_cell  # noqa: E402

CELLS = [("lstm_ml.asofed", "lstm_ml.asofed.k1024"),
         ("lstm_ml.asofed", "lstm_ml.asofed.k20"),
         ("cnn_fmnist.fedasync", "cnn_fmnist.fedasync.k256")]


@pytest.fixture(autouse=True)
def _isolated(monkeypatch):
    from repro.sim.workloads import WORKLOADS

    monkeypatch.setattr(WORKLOADS, "_entries", dict(WORKLOADS._entries))
    # no persistent compilation cache in a test process
    monkeypatch.setattr(run, "enable_cache", lambda: "off")


def _frozen_step(monkeypatch):
    import repro.sim.compile as compile_lib

    real = compile_lib.tick_body

    def body(*a, **k):
        tick = real(*a, **k)

        def frozen(stacked, server, *rest):
            _, _, tel = tick(stacked, server, *rest)
            return stacked, server, tel

        return frozen

    monkeypatch.setattr(compile_lib, "tick_body", body)


def _half_batch(monkeypatch):
    from repro.core.algorithms import asofed, fedasync

    def halve(build):
        def wrapped(*a, **k):
            fn = build(*a, **k)
            return lambda p, q, xs, ys: fn(p, q, xs[:, : xs.shape[1] // 2],
                                           ys[:, : ys.shape[1] // 2])
        return wrapped

    monkeypatch.setattr(asofed, "avg_surrogate_grad",
                        halve(asofed.avg_surrogate_grad))
    monkeypatch.setattr(fedasync, "sgd_epochs", halve(fedasync.sgd_epochs))


def _altered_answer(monkeypatch):
    from repro.sim.evaluation import Evaluator

    real = Evaluator.predict_device

    def altered(self, params):
        p = real(self, params)
        return p.at[0, 0].set(-p[0, 0])

    monkeypatch.setattr(Evaluator, "predict_device", altered)


FAULTS = {"frozen_step": _frozen_step, "half_batch": _half_batch,
          "altered_answer": _altered_answer}


def _run(cell, capsys):
    args = types.SimpleNamespace(seed=2 ** 31 + 21, seconds=0.0, trace=0)
    assert run.run_cell(cell, args, jax.devices()) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("name,limits_from", CELLS)
def test_sound_run_is_correct(name, limits_from, capsys):
    res = _run(small_cell(name, limits_from), capsys)
    assert res["correct"] is True, res
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"iters_per_s", "setup_s"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name,limits_from", CELLS)
def test_fault_is_not_correct(name, limits_from, fault, monkeypatch,
                              capsys):
    FAULTS[fault](monkeypatch)
    res = _run(small_cell(name, limits_from), capsys)
    assert res["correct"] is False, res
