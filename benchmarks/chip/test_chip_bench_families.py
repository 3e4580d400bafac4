"""A model family enters the benchmark as files alone, and the reference's
two seams for model-size clients: the run's cohort cap, and client states
held in host memory."""
import ast
import hashlib
import json
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import flops  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
from small_cells import small_cell  # noqa: E402

SEED = 2 ** 31 + 13
FAMILY_API = ("param_shapes", "first_layer", "forward", "loss",
              "train_flops_per_iter", "program_overrides", "report",
              "headline", "pred_block")


@pytest.fixture(autouse=True)
def _own_registry(monkeypatch):
    # the harness registers a workload per Program: keep them out of the
    # registry other tests of this process read
    from repro.sim.workloads import WORKLOADS

    monkeypatch.setattr(WORKLOADS, "_entries", dict(WORKLOADS._entries))


def _check(cell):
    seeds = harness.Seeds(SEED)
    data, offsets = harness.make_traffic(cell, seeds)
    out = harness.Program(cell, data, offsets, seeds).job()
    ref = harness.replay_for(cell, out, data, offsets, seeds)
    return out, ref, harness.compare(out, ref)


# a generator of the new family's own: labelled noisy sine sequences
GENERATOR = '''

import numpy as np


def sine_classes(n_clients, n_per, T, F, n_classes, seed):
    """Sequences whose frequency is their class, per-client phases."""
    out = []
    t = np.linspace(0.0, 2 * np.pi, T)[None, :, None]
    for c in range(n_clients):
        rng = np.random.default_rng(seed * 101 + c)
        y = rng.integers(0, n_classes, n_per).astype(np.int32)
        phase = rng.normal(0.0, 0.3, (n_per, 1, F))
        x = np.sin((y[:, None, None] + 1) * t + phase) \\
            + rng.normal(0.0, 0.2, (n_per, T, F))
        n_te = n_per // 4
        x = x.astype(np.float32)
        out.append((x[:-n_te], y[:-n_te], x[-n_te:], y[-n_te:]))
    return out


GENERATORS = {"sine_classes": sine_classes}
'''


def _harness_files():
    return {p.name: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in HERE.glob("*.py")}


def test_a_family_enters_by_files_alone(tmp_path, monkeypatch):
    """A new family (the paper's LSTM under another name, classifying
    sequences of a generator of its own) with its configuration, traffic
    and cell, all written to a directory of their own: the harness reaches
    it through the family's search path, and the program agrees with the
    reference."""
    before = _harness_files()
    for sub in ("families", "configs", "traffic", "cells"):
        (tmp_path / sub).mkdir()
    (tmp_path / "families" / "seqclass.py").write_text(
        (HERE / "families" / "lstm.py").read_text() + GENERATOR)
    (tmp_path / "configs" / "seqclass.tiny.json").write_text(json.dumps({
        "name": "seqclass.tiny",
        "model": {"arch": "paper-lstm", "family": "seqclass",
                  "task": "classification", "in_features": 8,
                  "out_features": 3, "hidden": 12},
        "dataset": {"generator": "sine_classes", "n_per": 40, "T": 10,
                    "F": 8, "n_classes": 3}}))
    tr = json.loads((HERE / "traffic" / "fedasync.k256.json").read_text())
    tr.update(clients=6)
    tr["run_config"]["window"] = 4
    (tmp_path / "traffic" / "fedasync.k6.json").write_text(json.dumps(tr))
    (tmp_path / "cells" / "test.seqclass.json").write_text(json.dumps({
        "T": 48, "eval_every": 12,
        "limits": {"sched_mismatch": 0, "loss_gap": 0.05,
                   "pred_gap": 0.25}}))
    monkeypatch.setattr(reference, "FAMILY_DIRS",
                        [tmp_path / "families", *reference.FAMILY_DIRS])

    cell = harness.load_cell("test.seqclass", "seqclass.tiny",
                             "fedasync.k6", root=tmp_path)
    fam = reference.family(cell.config["model"])
    assert Path(fam.__file__).parent == tmp_path / "families"
    out, ref, nums = _check(cell)
    assert nums["sched_mismatch"] == 0
    assert max(nums["loss_gap_all"], nums["pred_gap"]) < 1e-4, nums
    assert harness.judge(nums, cell.spec["limits"])
    assert ref.preds.shape == (6 * 10, 3)
    # the FLOP count is the new module's: 10 steps of the gates, the head
    assert flops.train_flops_per_iter(cell.config, cell.traffic) == \
        3 * 64 * (2 * 10 * (8 * 48 + 12 * 48) + 2 * 12 * 3)
    assert _harness_files() == before


@pytest.mark.parametrize("max_cohort,residency", [
    (1, "device"), (2, "device"), (1, "host")])
def test_reference_honours_the_cohort_cap(max_cohort, residency):
    cell = small_cell("cnn_fmnist.fedasync", "cnn_fmnist.fedasync.k256")
    cell.traffic["run_config"].update(max_cohort=max_cohort,
                                      state_residency=residency)
    out, ref, nums = _check(cell)
    assert nums["sched_mismatch"] == 0, nums
    assert max(nums["loss_gap_all"], nums["pred_gap"]) < 1e-4, nums
    assert max(n for _, n in ref.ticks) == max_cohort
    # without the cap the reference's ticks are the 4 clients' and differ
    run = dict(cell.traffic, **cell.traffic["run_config"])
    run["max_cohort"] = None
    seeds = harness.Seeds(SEED)
    data, offsets = harness.make_traffic(cell, seeds)
    uncapped = reference.replay(
        cell.config["model"], run, data, offsets, run_seed=seeds.run,
        stream_seed=seeds.streams, stop=int(out.history[0][0]))
    assert max(n for _, n in uncapped.ticks) > max_cohort
    out.ticks = [(t, n, l) for (t, n), l in zip(uncapped.ticks,
                                                uncapped.tick_loss)]
    assert harness.compare(out, ref)["sched_mismatch"] > 0


@pytest.mark.parametrize("name,limits_from,max_cohort,dtype", [
    ("cnn_fmnist.fedasync", "cnn_fmnist.fedasync.k256", None, jnp.float32),
    ("cnn_fmnist.fedasync", "cnn_fmnist.fedasync.k256", 2, jnp.float32),
    ("cnn_fmnist.fedasync", "cnn_fmnist.fedasync.k256", None, jnp.bfloat16),
    ("lstm_ml.asofed", "lstm_ml.asofed.k1024", None, jnp.float32),
])
def test_host_states_replay_bitwise(name, limits_from, max_cohort, dtype):
    cell = small_cell(name, limits_from)
    seeds = harness.Seeds(SEED)
    data, offsets = harness.make_traffic(cell, seeds)

    def replay(residency):
        run = dict(cell.traffic, **cell.traffic["run_config"])
        run.update(max_cohort=max_cohort, state_residency=residency)
        return reference.replay(
            cell.config["model"], run, data, offsets, run_seed=seeds.run,
            stream_seed=seeds.streams, stop=cell.spec["T"] // 2,
            dtype=dtype)

    dev, host = replay("device"), replay("host")
    assert dev.ticks == host.ticks and dev.sim_time == host.sim_time
    assert dev.tick_loss == host.tick_loss
    np.testing.assert_array_equal(dev.preds, host.preds)
    assert np.all(np.isfinite(dev.preds))


def _imports(path: Path):
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield node.module or ""


@pytest.mark.parametrize("path", sorted((HERE / "families").glob("*.py"))
                         + [HERE / "tasks.py", HERE / "reference.py"],
                         ids=lambda p: p.stem)
def test_no_family_module_imports_repro(path):
    mods = list(_imports(path))
    assert mods and not any(m.split(".")[0] == "repro" for m in mods), mods


@pytest.mark.parametrize("path", sorted((HERE / "configs").glob("*.json")),
                         ids=lambda p: p.stem)
def test_every_config_has_its_family_module(path):
    model = json.loads(path.read_text())["model"]
    fam = reference.family(model)
    assert all(callable(getattr(fam, f)) for f in FAMILY_API)
    assert set(fam.program_overrides(model)) <= set(model)
    assert fam.pred_block(model) > 0


@pytest.mark.parametrize("name", ["reference.py", "flops.py", "traffic.py",
                                  "harness.py"])
def test_dispatchers_name_no_family_or_task(name):
    src = (HERE / name).read_text()
    for word in ("lstm", "cnn", "regression", "classification",
                 "multilabel"):
        for quote in "\"'":
            assert f"{quote}{word}{quote}" not in src, (name, word)


def test_an_unknown_family_is_an_error():
    with pytest.raises(KeyError, match="no family module"):
        reference.family({"family": "no-such-family"})
