"""FLOP counts against hand counts from the shapes, and the peak
table."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import flops  # noqa: E402
import peaks  # noqa: E402


def _config(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def test_lstm_forward_flops_by_hand():
    # the paper's LSTM at hidden 64 on 48 steps of 8 features, one output
    m = {"family": "lstm", "in_features": 8, "out_features": 1,
         "hidden": 64}
    # 48 steps of the 8->256 and 64->256 gate projections, then the head
    assert flops.forward_flops_per_sample(m, 48) == \
        2 * 48 * (8 * 256 + 64 * 256) + 2 * 64 * 1
    # about 5.3 MFLOP a sample trained
    assert 5.2e6 < 3 * flops.forward_flops_per_sample(m, 48) < 5.4e6
    cfg = {"model": dict(m, in_features=32, out_features=6),
           "dataset": {"T": 16}}
    assert flops.seq_len(cfg) == 16
    assert flops.forward_flops_per_sample(cfg["model"], 16) == \
        2 * 16 * (32 * 256 + 64 * 256) + 2 * 64 * 6


def test_cnn_forward_flops_by_hand():
    m = _config("paper-cnn.fmnist")["model"]
    conv2 = 2 * 28 * 28 * 9 * 32 * 32
    assert flops.forward_flops_per_sample(m) == \
        2 * 28 * 28 * 9 * 1 * 32 + conv2 + 2 * 14 * 14 * 32 * 10
    # conv2 is nearly all of it: about 45 MFLOP a sample trained
    assert 44e6 < 3 * flops.forward_flops_per_sample(m) < 46e6


def test_train_flops_per_iter():
    cfg = _config("paper-cnn.fmnist")
    tr = {"run_config": {"local_epochs": 2, "batch_size": 32}}
    assert flops.train_flops_per_iter(cfg, tr) == \
        3 * 64 * flops.forward_flops_per_sample(cfg["model"])


def test_peak_table_v5e():
    p = peaks.peak("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_peak_table_unknown_kind_is_an_error():
    with pytest.raises(KeyError, match="no peak rates"):
        peaks.peak("TPU v9 imaginary")
