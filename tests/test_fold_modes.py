"""Server fold-mode equivalence + FedBuff buffered-aggregation tests.

Contracts pinned here:

* ``fold_mode="sequential"`` (the default) is the bitwise oracle;
  ``fold_mode="associative"`` replays the same trajectory within fp
  tolerance for every affine strategy, window size, seed, and trace —
  and *bitwise* on single-fold ticks, which run the sequential step
  itself (a one-slot bucket has no prefix to parallelize);
* ``"auto"`` degrades to the sequential scan on CPU (bitwise);
* forcing ``"associative"`` on a non-affine fold (asofed with the
  Eq. 5-6 feature pass) fails fast, as does a typo'd mode;
* fedbuff matches its per-arrival host oracle under always-on and traced
  scenarios for all three registered workloads, including the buffer
  boundary cases (M=1, M larger than the whole run, clients retiring
  mid-buffer).
"""
import dataclasses

import numpy as np
import pytest
import jax

from repro.core.algorithms import get_strategy
from repro.sim.engine import run_strategy
from repro.sim.reference import run_fedbuff_reference
from repro.sim.telemetry import TelemetryLog
from repro.sim.traces import AvailabilityTrace, scenario_traces
from repro.sim.workloads import get_workload

WL = get_workload("lstm_regression")

CFG = WL.run_config(T=48, batch_size=8, local_epochs=2, eta=0.02, lam=1.0,
                    beta=0.001, eval_every=24, seed=0)

# (strategy, config overrides making its fold affine)
AFFINE = [
    ("fedasync", {}),
    ("asofed", {"feature_learning": False}),
    ("fedbuff", {"buffer_size": 3}),
]


def _setup(n_clients=5, n_per=60):
    cfg_model, model = WL.build(hidden=12)
    return cfg_model, model, lambda traces=None: WL.make_clients(
        n_clients, n_per=n_per, seed=0, traces=traces)


def _trace(alg, model, cfg_model, clients, cfg, **kw):
    tr = []
    run_strategy(get_strategy(alg), model, cfg_model, clients, cfg,
                 trace=tr, **kw)
    return tr


def _assert_traces_close(a, b, *, atol=3e-4, rtol=3e-3, tag=""):
    assert len(a) == len(b) >= 2
    for (t1, w1), (t2, w2) in zip(a, b):
        assert t1 == t2, tag
        for x, y in zip(jax.tree.leaves(w1), jax.tree.leaves(w2)):
            np.testing.assert_allclose(x, y, atol=atol, rtol=rtol,
                                       err_msg=f"{tag} t={t1}")


def _assert_traces_bitwise(a, b, *, tag=""):
    assert len(a) == len(b) >= 2
    for (t1, w1), (t2, w2) in zip(a, b):
        assert t1 == t2, tag
        for x, y in zip(jax.tree.leaves(w1), jax.tree.leaves(w2)):
            np.testing.assert_array_equal(x, y, err_msg=f"{tag} t={t1}")


# ---------------------------------------------------------------------------
# associative == sequential: strategies x windows x traces (x seeds: slow)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("alg,over", AFFINE)
@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("window", [1, 6])
def test_associative_matches_sequential(alg, over, traced, window):
    cfg_model, model, mk = _setup()
    traces = (scenario_traces("diurnal", 5, seed=0, period=150.0, duty=0.55)
              if traced else None)
    cfg = dataclasses.replace(CFG, **over)
    seq = _trace(alg, model, cfg_model, mk(traces), cfg, window=window)
    par = _trace(alg, model, cfg_model, mk(traces),
                 dataclasses.replace(cfg, fold_mode="associative"),
                 window=window)
    _assert_traces_close(seq, par,
                         tag=f"{alg} traced={traced} window={window}")


@pytest.mark.slow
@pytest.mark.parametrize("alg,over", AFFINE)
@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("window", [3, 32])
def test_associative_matches_sequential_sweep(alg, over, seed, window):
    """The wider property sweep (seeds x windows) behind --runslow."""
    cfg_model, model, mk = _setup()
    cfg = dataclasses.replace(CFG, seed=seed, **over)
    seq = _trace(alg, model, cfg_model, mk(), cfg, window=window)
    par = _trace(alg, model, cfg_model, mk(),
                 dataclasses.replace(cfg, fold_mode="associative"),
                 window=window)
    _assert_traces_close(seq, par, tag=f"{alg} seed={seed} window={window}")


def test_associative_single_fold_bitwise():
    """max_cohort=1 ticks hold exactly one fold, which the associative
    mode runs through the sequential step itself, so fedasync must be
    bit-identical — fp reassociation only enters at fold depth >= 2."""
    cfg_model, model, mk = _setup()
    seq = _trace("fedasync", model, cfg_model, mk(), CFG, max_cohort=1)
    par = _trace("fedasync", model, cfg_model, mk(),
                 dataclasses.replace(CFG, fold_mode="associative"),
                 max_cohort=1)
    _assert_traces_bitwise(seq, par, tag="single-fold")


def test_auto_is_sequential_on_cpu():
    """'auto' keeps the bitwise sequential scan on CPU backends."""
    if jax.default_backend() != "cpu":
        pytest.skip("auto resolves to associative on accelerators")
    cfg_model, model, mk = _setup()
    seq = _trace("fedasync", model, cfg_model, mk(), CFG, window=4)
    aut = _trace("fedasync", model, cfg_model, mk(),
                 dataclasses.replace(CFG, fold_mode="auto"), window=4)
    _assert_traces_bitwise(seq, aut, tag="auto-cpu")


@pytest.mark.slow
def test_associative_fold_kernel_interpret_in_engine():
    """The Pallas lowering of the affine fold, exercised end-to-end on
    CPU through the interpreter (the TPU kernel's CI hook)."""
    cfg_model, model, mk = _setup()
    cfg = dataclasses.replace(CFG, T=24, eval_every=12)
    seq = _trace("fedasync", model, cfg_model, mk(), cfg, window=4)
    par = _trace("fedasync", model, cfg_model, mk(),
                 dataclasses.replace(cfg, fold_mode="associative",
                                     fold_kernel=True,
                                     fold_kernel_interpret=True),
                 window=4)
    _assert_traces_close(seq, par, tag="fold_kernel interpret")


# ---------------------------------------------------------------------------
# fail-fast validation
# ---------------------------------------------------------------------------


def test_associative_requires_affine_fold():
    """asofed with the (non-affine) feature pass declines; forcing the
    mode must raise before any compile cost."""
    cfg_model, model, mk = _setup(n_clients=3)
    cfg = dataclasses.replace(CFG, fold_mode="associative")  # feature on
    with pytest.raises(ValueError, match="declines the affine fold"):
        run_strategy(get_strategy("asofed"), model, cfg_model, mk(), cfg)


def test_unknown_fold_mode_fails_fast():
    cfg_model, model, mk = _setup(n_clients=3)
    cfg = dataclasses.replace(CFG, fold_mode="parallel")
    with pytest.raises(ValueError, match="unknown fold_mode"):
        run_strategy(get_strategy("fedasync"), model, cfg_model, mk(), cfg)


def test_foldless_strategies_accept_any_mode():
    """local/global have no server fold: every mode degrades to a no-op
    rather than raising."""
    cfg_model, model, mk = _setup(n_clients=3)
    cfg = dataclasses.replace(CFG, T=4, eval_every=2,
                              fold_mode="associative")
    hist = run_strategy(get_strategy("local"), model, cfg_model, mk(), cfg)
    assert hist


# ---------------------------------------------------------------------------
# fedbuff: engine vs per-arrival oracle
# ---------------------------------------------------------------------------


def _assert_matches_oracle(trace, ref, *, atol=3e-4, rtol=3e-3, tag=""):
    checked = 0
    for t, w in trace:
        if t not in ref:
            continue
        for x, y in zip(jax.tree.leaves(w), jax.tree.leaves(ref[t])):
            np.testing.assert_allclose(x, y, atol=atol, rtol=rtol,
                                       err_msg=f"{tag} t={t}")
        checked += 1
    assert checked >= 2, tag


@pytest.mark.parametrize("workload", ["lstm_regression", "cnn_classification",
                                      "lstm_multilabel"])
@pytest.mark.parametrize("traced", [False, True])
def test_fedbuff_engine_matches_oracle(workload, traced):
    wl = get_workload(workload)
    cfg_model, model = wl.build(hidden=8)
    traces = (scenario_traces("diurnal", 5, seed=0, period=150.0, duty=0.55)
              if traced else None)
    mk = lambda: wl.make_clients(5, seed=0, traces=traces)  # noqa: E731
    cfg = wl.run_config(T=36, batch_size=8, local_epochs=2, eta=0.02,
                        lam=1.0, beta=0.001, eval_every=18, seed=0,
                        buffer_size=3)
    ref = run_fedbuff_reference(model, cfg_model, mk(), cfg)
    tr = _trace("fedbuff", model, cfg_model, mk(), cfg, window=4)
    _assert_matches_oracle(tr, ref, tag=f"{workload} traced={traced}")


@pytest.mark.parametrize("buffer_size", [1, 1000])
def test_fedbuff_buffer_boundaries(buffer_size):
    """M=1 flushes every fold (fedbuff degrades to per-arrival steps);
    M > folds-in-run never flushes — the central model stays w0 bitwise
    while the buffer fill climbs."""
    cfg_model, model, mk = _setup()
    cfg = dataclasses.replace(CFG, buffer_size=buffer_size)
    ref = run_fedbuff_reference(model, cfg_model, mk(), cfg)
    tr = []
    tel = TelemetryLog()
    run_strategy(get_strategy("fedbuff"), model, cfg_model, mk(), cfg,
                 trace=tr, telemetry=tel, window=4)
    _assert_matches_oracle(tr, ref, tag=f"M={buffer_size}")
    _, fill = tel.curve("buffer_fill")
    cum = np.cumsum([r.n_folds for r in tel.records])
    np.testing.assert_array_equal(fill, (cum % buffer_size).astype(np.float32))
    if buffer_size == 1000:
        w0 = model.init(jax.random.PRNGKey(cfg.seed))
        for x, y in zip(jax.tree.leaves(tr[-1][1]), jax.tree.leaves(w0)):
            np.testing.assert_array_equal(x, y)


def test_fedbuff_retired_clients_mid_buffer():
    """One-shot traces retire three of five clients partway through the
    run: deposits from retired clients stay in the buffer and fold into
    the next flush, identically in engine and oracle."""
    cfg_model, model, mk = _setup()
    traces = [AvailabilityTrace(windows=((0.0, 120.0),)),
              AvailabilityTrace(windows=((0.0, 180.0),)),
              None,
              AvailabilityTrace(windows=((0.0, 150.0),)),
              None]
    cfg = dataclasses.replace(CFG, buffer_size=4)
    ref_stats, eng_stats = {}, {}
    ref = run_fedbuff_reference(model, cfg_model, mk(traces), cfg,
                                stats=ref_stats)
    tr = []
    run_strategy(get_strategy("fedbuff"), model, cfg_model, mk(traces), cfg,
                 trace=tr, stats=eng_stats, window=4)
    assert eng_stats["retired_clients"] >= 1
    assert eng_stats["retired_clients"] == ref_stats["retired_clients"]
    _assert_matches_oracle(tr, ref, tag="retired-mid-buffer")


def test_fedbuff_associative_matches_oracle():
    """Transitivity check made explicit: the associative closed form of
    the buffered fold also lands on the per-arrival oracle."""
    cfg_model, model, mk = _setup()
    cfg = dataclasses.replace(CFG, buffer_size=3, fold_mode="associative")
    ref = run_fedbuff_reference(model, cfg_model, mk(),
                                dataclasses.replace(cfg,
                                                    fold_mode="sequential"))
    tr = _trace("fedbuff", model, cfg_model, mk(), cfg, window=6)
    _assert_matches_oracle(tr, ref, tag="fedbuff-associative")
