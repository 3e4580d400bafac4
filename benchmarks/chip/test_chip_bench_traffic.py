"""The benchmark's traffic copies equal the program's generators at equal
seeds, so the yardstick starts where the program's data module stands."""
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import traffic  # noqa: E402
from repro.data import synthetic  # noqa: E402
from repro.sim.profiles import make_profiles  # noqa: E402


def _same(a, b):
    assert len(a) == len(b)
    for qa, qb in zip(a, b):
        for x, y in zip(qa, qb):
            assert x.dtype == y.dtype and x.shape == y.shape
            np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 11])
def test_airquality_copy(seed):
    _same(traffic.airquality_like(n_clients=5, n_per=40, seed=seed),
          synthetic.airquality_like(n_clients=5, n_per=40, seed=seed))


@pytest.mark.parametrize("n_clients", [6, 20])
def test_fmnist_copy(n_clients):
    _same(traffic.fmnist_like(n_clients=n_clients, scale=0.01, seed=7),
          synthetic.fmnist_like(n_clients=n_clients, scale=0.01, seed=7))


def test_extrasensory_multilabel_copy():
    _same(traffic.extrasensory_multilabel_like(n_clients=4, n_per=20,
                                               seed=2 ** 31 + 3),
          synthetic.extrasensory_multilabel_like(n_clients=4, n_per=20,
                                                 seed=2 ** 31 + 3))


def test_network_offsets_are_one_fleet_in_seeded_orders():
    a = traffic.network_offsets(33, 0, (10.0, 100.0))
    b = traffic.network_offsets(33, 2 ** 31 + 5, (10.0, 100.0))
    assert a != b and sorted(a) == sorted(b)
    assert a == traffic.network_offsets(33, 0, (10.0, 100.0))
    # the same range, mean and spread as make_profiles' uniform draws
    assert 10.0 < min(a) and max(a) < 100.0
    assert np.mean(a) == pytest.approx(55.0)
    draws = [p.base_delay for p in make_profiles(4000, seed=1)]
    assert np.std(traffic.network_offsets(4000, 1, (10.0, 100.0))) == \
        pytest.approx(np.std(draws), rel=0.02)


def test_make_datasets_reads_the_config_entry():
    data = traffic.make_datasets(
        {"generator": "airquality_like", "n_per": 40, "T": 12, "F": 8}, 3, 5)
    _same(data, synthetic.airquality_like(n_clients=3, n_per=40, T=12, F=8,
                                          seed=5))
