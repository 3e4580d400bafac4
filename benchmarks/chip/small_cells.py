"""Cells cut to CPU-test size: few clients, few samples per client, narrow
models, a short job and a short window.

``cnn_fmnist.fedasync`` is the benchmark's CNN cell from its own files.
``lstm_ml.asofed`` is a test cell only, so that the check's ASO-Fed path
(the sequential fold with the feature pass) stays tested while no
benchmark cell runs ASO-Fed: a multilabel LSTM on the ``asofed.k1024``
traffic, checked against the limits calibrated on the chip for the
multilabel LSTM cells that ``PERF.md`` lists under Open questions.
"""
from __future__ import annotations

import copy
import json

import harness

LSTM_ML = {
    "name": "test.lstm_ml",
    "model": {"arch": "paper-lstm", "family": "lstm", "task": "multilabel",
              "in_features": 32, "out_features": 6, "hidden": 16},
    "dataset": {"generator": "extrasensory_multilabel_like", "n_per": 48,
                "T": 16, "F": 32, "n_classes": 6},
}

LSTM_ML_LIMITS = {
    "lstm_ml.asofed.k1024": {"sched_mismatch": 0, "loss_gap_all": 5e-4,
                             "pred_gap": 0.25},
    "lstm_ml.asofed.k20": {"sched_mismatch": 0, "loss_gap_all": 1e-3,
                           "pred_gap": 0.2},
}

SMALL = {
    # cell -> (config, traffic, clients, T, dataset and model overrides)
    "lstm_ml.asofed": (LSTM_ML, "asofed.k1024", 5, 64, {}, {}),
    "cnn_fmnist.fedasync": ("paper-cnn.fmnist", "fedasync.k256", 4, 48,
                            {"scale": 0.015}, {"hidden": 4}),
}


def small_cell(name: str, limits_from: str, **traffic) -> harness.Cell:
    """``name`` of ``SMALL`` at test size, checked against the limits of
    the cell ``limits_from``.  ``traffic`` overrides keys of the traffic
    file, or of its ``run_config`` where they are the program's knobs."""
    config, tr_name, K, T, ds, model = SMALL[name]
    if isinstance(config, str):
        config = harness._read(harness.HERE / "configs" / f"{config}.json")
    cfg = copy.deepcopy(config)
    cfg["dataset"].update(ds)
    cfg["model"].update(model)
    tr = harness._read(harness.HERE / "traffic" / f"{tr_name}.json")
    tr.update(clients=K)
    tr["run_config"]["window"] = 4
    for k, v in traffic.items():
        (tr["run_config"] if k in tr["run_config"] else tr)[k] = v
    if limits_from in LSTM_ML_LIMITS:
        spec = {"limits": dict(LSTM_ML_LIMITS[limits_from])}
    else:
        spec = json.loads(
            (harness.HERE / "cells" / f"{limits_from}.json").read_text())
    spec.update(T=T, eval_every=T // 4)
    return harness.Cell(name=f"small.{name}", chips=1, config=cfg,
                        traffic=tr, spec=spec)
