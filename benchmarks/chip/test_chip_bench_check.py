"""The check at CPU-test size: the engine agrees with the plain reference,
and the control (the reference one precision step down, bfloat16, in the
program's place) fails the committed limits."""
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import harness  # noqa: E402
import reference  # noqa: E402
from small_cells import small_cell  # noqa: E402

SEED = 2 ** 31 + 9  # wider than 32 signed bits, as benchmark seeds may be


@pytest.fixture(autouse=True)
def _own_registry(monkeypatch):
    # the harness registers a workload per Program: keep them out of the
    # registry other tests of this process read
    from repro.sim.workloads import WORKLOADS

    monkeypatch.setattr(WORKLOADS, "_entries", dict(WORKLOADS._entries))


def _job(cell):
    seeds = harness.Seeds(SEED)
    data, offsets = harness.make_traffic(cell, seeds)
    out = harness.Program(cell, data, offsets, seeds).job()
    return out, data, offsets, seeds


@pytest.mark.parametrize("name,limits_from,traffic", [
    ("lstm_ml.asofed", "lstm_ml.asofed.k1024", {}),
    ("cnn_fmnist.fedasync", "cnn_fmnist.fedasync.k256", {}),
    ("cnn_fmnist.fedasync", "cnn_fmnist.fedasync.k256",
     {"fold_mode": "associative"}),
])
def test_engine_matches_reference(name, limits_from, traffic):
    cell = small_cell(name, limits_from, **traffic)
    out, data, offsets, seeds = _job(cell)
    ref = harness.replay_for(cell, out, data, offsets, seeds)
    nums = harness.compare(out, ref)
    assert nums["sched_mismatch"] == 0
    assert max(nums["loss_gap_all"], nums["pred_gap"]) < 1e-4, nums
    assert harness.judge(nums, cell.spec["limits"])


@pytest.mark.parametrize("name,limits_from,T", [
    ("lstm_ml.asofed", "lstm_ml.asofed.k1024", 256),
    ("lstm_ml.asofed", "lstm_ml.asofed.k20", 256),
    ("cnn_fmnist.fedasync", "cnn_fmnist.fedasync.k256", 160),
])
def test_control_fails(name, limits_from, T):
    # a longer job than the other tests': the control parts from the
    # reference as bfloat16 rounding compounds over the folds
    cell = small_cell(name, limits_from)
    cell.spec.update(T=T, eval_every=T // 4)
    out, data, offsets, seeds = _job(cell)
    ref = harness.replay_for(cell, out, data, offsets, seeds)
    control = harness.replay_for(cell, out, data, offsets, seeds,
                                 dtype=jnp.bfloat16)
    # the control's answers in the program's place
    out.preds = [control.preds]
    out.ticks = [(t, n, l) for (t, n), l in
                 zip(control.ticks, control.tick_loss)]
    nums = harness.compare(out, ref)
    assert not harness.judge(nums, cell.spec["limits"]), nums


def test_reference_init_is_the_seeded_fan_in_rule():
    import jax

    from repro.configs import get_arch
    from repro.models import LOCAL, build_model
    import dataclasses

    m = harness._read(HERE / "configs" / "paper-cnn.fmnist.json")["model"]
    cfg = dataclasses.replace(get_arch("paper-cnn"), hidden=m["hidden"])
    w_prog = build_model(cfg, LOCAL).init(jax.random.PRNGKey(SEED % 2 ** 31))
    w_ref = reference.init_params(m, SEED % 2 ** 31)
    assert sorted(w_prog) == sorted(w_ref)
    for k in w_ref:
        assert (w_prog[k] == w_ref[k]).all(), k
