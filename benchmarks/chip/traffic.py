"""Traffic of the chip benchmark: client data and network offsets from a seed.

The generators are copies of ``repro.data.synthetic`` (the benchmark's
yardstick may not move when the program's data module does);
``test_chip_bench_traffic.py`` pins each copy to the program's original at
equal seeds.  The network offsets cover ``U[delay_range]`` as evenly
spaced quantiles in an order drawn from the seed, so that every seed
simulates the same fleet and only its order changes: independent uniform
draws (``repro.sim.profiles.make_profiles``) change the tick sizes, and
with them the work, from seed to seed.

Every generator returns ``[(x_train, y_train, x_test, y_test)] * n_clients``.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

Quad = Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]


def _split(x, y, test_frac=0.25) -> Quad:
    n = len(x)
    n_te = max(1, int(n * test_frac))
    return x[:-n_te], y[:-n_te], x[-n_te:], y[-n_te:]


def _ar1_sequences(rng, n, T, F, phi, noise, bias):
    x = np.zeros((n, T, F), np.float32)
    eps = rng.normal(0, noise, size=(n, T, F))
    x[:, 0] = bias + eps[:, 0]
    for t in range(1, T):
        x[:, t] = bias + phi * (x[:, t - 1] - bias) + eps[:, t]
    return x.astype(np.float32)


def airquality_like(n_clients: int = 9, n_per: int = 600, T: int = 48,
                    F: int = 8, seed: int = 1) -> List[Quad]:
    """Station clients, weather -> pollutant regression (AR(1) sensors
    with a seasonal channel and a per-station phase and bias)."""
    out = []
    for c in range(n_clients):
        crng = np.random.default_rng(seed * 777 + c)
        season_phase = crng.uniform(0, 2 * np.pi)
        bias = crng.normal(0, 0.8, size=F)
        x = _ar1_sequences(crng, n_per, T, F, 0.85, 0.25, bias)
        tt = np.linspace(0, 4 * np.pi, T)
        x[:, :, 0] += np.sin(tt + season_phase)[None, :]
        wind, temp = x[:, -1, 1], x[:, -1, 0]
        y = (
            3.0
            - 1.2 * wind
            - 0.8 * temp
            + 0.3 * x[:, -4:].mean(axis=(1, 2))
            + 0.15 * crng.normal(size=n_per)
        ).astype(np.float32)
        out.append(_split(x, y))
    return out


def extrasensory_multilabel_like(n_clients: int = 20, n_per: int = 300,
                                 T: int = 16, F: int = 32,
                                 n_classes: int = 6, seed: int = 2
                                 ) -> List[Quad]:
    """Multi-label activity recognition with per-user label skew: each
    sample activates 1-3 of the user's 2-4 activities; ``y`` is the
    (n, C) multi-hot mask and ``x`` superimposes the active prototypes."""
    base_rng = np.random.default_rng(seed)
    protos = base_rng.normal(0, 1.0, size=(n_classes, F)).astype(np.float32)
    out = []
    for c in range(n_clients):
        crng = np.random.default_rng(seed * 31 + c)
        k = int(crng.integers(2, 5))
        classes = crng.choice(n_classes, size=k, replace=False)
        y = np.zeros((n_per, n_classes), np.float32)
        x = np.zeros((n_per, T, F), np.float32)
        user_shift = crng.normal(0, 0.5, size=F)
        for i in range(n_per):
            m = int(crng.integers(1, min(3, k) + 1))
            active = crng.choice(classes, size=m, replace=False)
            y[i, active] = 1.0
            drift = np.linspace(0, 1, T)[:, None] * crng.normal(0, 0.2, size=F)
            x[i] = (
                protos[active].sum(axis=0)[None, :]
                + user_shift[None, :]
                + drift
                + crng.normal(0, 0.6, size=(T, F))
            )
        out.append(_split(x, y))
    return out


def _digit_pattern(label: int) -> np.ndarray:
    yy, xx = np.mgrid[0:28, 0:28] / 27.0
    base = (
        np.sin((label + 1) * np.pi * xx)
        + np.cos((label + 2) * np.pi * yy)
        + 0.5 * np.sin((label + 1) * 2 * np.pi * (xx + yy))
    )
    return base.astype(np.float32)


def fmnist_like(n_clients: int = 20, scale: float = 0.1, seed: int = 3
                ) -> List[Quad]:
    """Paper §5.1 partition: label shards of sizes {2000, 2750, 3250,
    4000} * scale, two per client; the label axis cycles fastest when the
    cohort is not the paper's 20 clients."""
    rng = np.random.default_rng(seed)
    sizes = [max(int(s), 4)
             for s in (np.array([2000, 2750, 3250, 4000]) * scale)]
    if n_clients == 20:
        shards = [(label, s) for label in range(10) for s in sizes]
    else:
        shards = [(i % 10, sizes[(i // 10) % len(sizes)])
                  for i in range(2 * n_clients)]
    rng.shuffle(shards)
    out = []
    for c in range(n_clients):
        xs, ys = [], []
        for label, n in shards[2 * c: 2 * c + 2]:
            pat = _digit_pattern(label)
            x = pat[None] + rng.normal(0, 0.4, size=(n, 28, 28)).astype(
                np.float32)
            xs.append(x[..., None])
            ys.append(np.full(n, label, np.int32))
        x = np.concatenate(xs)
        y = np.concatenate(ys)
        perm = rng.permutation(len(x))
        out.append(_split(x[perm], y[perm]))
    return out


GENERATORS: Dict[str, Callable[..., List[Quad]]] = {
    "airquality_like": airquality_like,
    "extrasensory_multilabel_like": extrasensory_multilabel_like,
    "fmnist_like": fmnist_like,
}


def make_datasets(dataset: dict, n_clients: int, seed: int) -> List[Quad]:
    """The configuration's ``dataset`` entry (generator name and its
    keyword arguments) for ``n_clients`` clients at ``seed``."""
    kw = {k: v for k, v in dataset.items() if k != "generator"}
    return GENERATORS[dataset["generator"]](n_clients=n_clients, seed=seed,
                                            **kw)


def network_offsets(n: int, seed: int,
                    delay_range: Sequence[float]) -> List[float]:
    """Per-client mean network offsets, seconds: the ``n`` midpoint
    quantiles of ``U[delay_range]``, permuted by ``default_rng(seed)``."""
    lo, hi = delay_range
    grid = lo + (hi - lo) * (np.arange(n) + 0.5) / n
    return [float(v) for v in np.random.default_rng(seed).permutation(grid)]
