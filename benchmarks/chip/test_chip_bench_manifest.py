"""``BENCHMARK.json`` against the files it names and the contract's rules
on names and units, and ``run.py``'s refusal to run without a TPU."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1].startswith("benchmarks/chip/")
    assert (ROOT / BENCH["command"][1]).is_file()
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_and_units():
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in BENCH[key]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    assert all(NAME.match(n) for n in names), names
    for key in ("end_to_end", "per_layer"):
        for m in BENCH[key]:
            assert UNIT.match(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
    metric_names = [m["name"] for k in ("end_to_end", "per_layer")
                    for m in BENCH[k]]
    assert len(set(metric_names)) == len(metric_names)


def test_every_cell_names_files_that_exist():
    configs = {c["name"]: c for c in BENCH["configs"]}
    used = set()
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4)
        cfg = configs[w["config"]]
        used.add(w["config"])
        assert cfg["file"].startswith("benchmarks/chip/configs/")
        assert json.loads((ROOT / cfg["file"]).read_text())["name"] == \
            cfg["name"]
        assert (HERE / "traffic" / f"{w['traffic']}.json").is_file()
        spec = json.loads((HERE / "cells" / f"{w['name']}.json").read_text())
        assert spec["eval_every"] > 0 and spec["T"] >= spec["eval_every"]
        assert {"sched_mismatch", "pred_gap"} <= set(spec["limits"]) <= {
            "sched_mismatch", "loss_gap", "loss_gap_all", "pred_gap"}
        assert spec["limits"]["sched_mismatch"] == 0
    assert used == set(configs)


@pytest.mark.parametrize("path", sorted((HERE / "traffic").glob("*.json")),
                         ids=lambda p: p.stem)
def test_every_traffic_run_config_is_a_run_config(path):
    # the harness hands run_config to the program as it is: every key has
    # to be one of RunConfig's, and the strategy one the reference replays
    import dataclasses

    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import RunConfig

    tr = json.loads(path.read_text())
    fields = {f.name for f in dataclasses.fields(RunConfig)}
    assert set(tr["run_config"]) <= fields - {"T", "eval_every", "seed",
                                              "task", "workload"}
    assert tr["strategy"] in ("asofed", "fedasync")
    RunConfig(**tr["run_config"])


def test_every_per_layer_metric_has_a_reader():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        reader = HERE / "metrics" / f"{m['name']}.py"
        assert reader.is_file(), reader
        assert "def read(ctx)" in reader.read_text()
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells


def test_end_to_end_bounds():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_a_full_check_fits_its_time():
    s = BENCH["run_seconds"]
    assert 2 + 14 * 24 * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = BENCH["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "no TPU" in p.stderr
