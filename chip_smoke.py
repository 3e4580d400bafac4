"""Smoke run of the ASO-Fed cohort engine on a TPU, through its entry point.

    python3 chip_smoke.py             # phases (a)-(d) on one chip
    python3 chip_smoke.py --chips 4   # phase (a) and fedasync, each sharded
                                      # over four chips and on one device

Every phase drives ``repro.core.run`` (the asynchronous cohort engine and its
fused megastep) on the paper's LSTM at its published width (hidden 64) over
``airquality_like`` streams (600 samples per client, 48 steps, 8 features):

  (a) ASO-Fed, 256 clients, 4096 global iterations, window 32, feature
      learning on, device-resident client state, the default fold mode; the
      eval MAE must be finite and fall from the first eval to the last.
  (b) fedasync with ``fold_mode="auto"`` (on a TPU the associative fold on
      the Pallas ``linear_scan`` kernel) against ``fold_mode="sequential"``.
  (c) ASO-Fed with ``feature_kernel=True`` (the Pallas ``feature_attention``
      kernel) against ``feature_kernel=False``.
  (d) the engine against the per-arrival oracle ``repro.sim.reference``.

The comparisons use the tolerances of the repo's equivalence tests.  Each
phase prints one ``smoke`` line (iterations, compile seconds apart from run
seconds, final metric): smoke output, not benchmark numbers.  The last line
is ``{"ok": true, "device": {...}}``.  Without a TPU, or when any phase fails
or misses its tolerance, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.common.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.core import run  # noqa: E402
from repro.kernels.feature_attention.ops import feature_attention  # noqa: E402
from repro.kernels.linear_scan.ops import fold_prefix  # noqa: E402
from repro.sim.reference import run_asofed_reference  # noqa: E402
from repro.sim.telemetry import TelemetryLog  # noqa: E402
from repro.sim.workloads import get_workload  # noqa: E402

# the equivalence tests' bounds: tests/test_fold_modes.py and
# tests/test_sim_engine.py (trajectories), tests/test_sharded_engine.py
# (sharded against one device, max abs error)
ATOL, RTOL = 3e-4, 3e-3
SHARDED_MAX_ERR = 3e-4

WL = get_workload("lstm_regression")


@dataclasses.dataclass(frozen=True)
class Sizes:
    clients: int = 256
    n_per: int = 600
    iters: int = 4096  # phase (a)
    cmp_iters: int = 1024  # phases (b) and (c), each run twice
    window: int = 32
    ref_clients: int = 16  # phase (d)
    ref_iters: int = 64


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (persistent-cache
    reads included), and the number of persistent-cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def _model():
    return WL.build(hidden=get_arch("paper-lstm").hidden)


def _engine_run(alg, model, cfg_model, n_clients, n_per, cfg, **kw):
    trace, stats, tel = [], {}, TelemetryLog()
    hist = run(alg, model, cfg_model, WL.make_clients(n_clients, n_per=n_per),
               cfg, trace=trace, stats=stats, telemetry=tel, **kw)
    return hist, trace, stats, tel


def _max_err(a, b):
    """Largest |a - b| over matched trace points; raises unless both
    traces hold the same fold counts and every leaf is within the tests'
    allclose tolerance."""
    _check(len(a) == len(b) >= 2, f"trace lengths {len(a)} vs {len(b)}")
    err = 0.0
    for (t1, w1), (t2, w2) in zip(a, b):
        _check(t1 == t2, f"trace points t={t1} vs t={t2}")
        for x, y in zip(jax.tree.leaves(w1), jax.tree.leaves(w2)):
            _check(bool(np.all(np.isfinite(x))), f"non-finite weights t={t1}")
            np.testing.assert_allclose(x, y, atol=ATOL, rtol=RTOL,
                                       err_msg=f"t={t1}")
            err = max(err, float(np.max(np.abs(x - y))))
    return err


def _assert_kernel(fn, *args):
    """The compiled program of ``fn`` at these shapes holds a Mosaic
    kernel (``tpu_custom_call``), not a reference lowering."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    _check("tpu_custom_call" in text, "no compiled Pallas kernel")


def _phase(name, clock, fn):
    c0, h0, w0 = clock.seconds, clock.cache_hits, time.perf_counter()
    out = fn()
    wall = time.perf_counter() - w0
    compile_s = clock.seconds - c0
    line = {"smoke": name, "note": "smoke output, not a benchmark",
            "compile_s": round(compile_s, 3),
            "run_s": round(wall - compile_s, 3),
            "cache_hits": clock.cache_hits - h0, **out}
    print(json.dumps(line), flush=True)


def phase_a(sz: Sizes, mesh="auto"):
    """ASO-Fed at the paper width; returns (summary, trace)."""
    cfg_model, model = _model()
    cfg = WL.run_config(T=sz.iters, eval_every=sz.iters // 4,
                        window=sz.window, seed=0)
    hist, trace, stats, _ = _engine_run("asofed", model, cfg_model,
                                        sz.clients, sz.n_per, cfg, mesh=mesh)
    mae = [h.metrics["mae"] for h in hist]
    _check(len(mae) >= 2, f"{len(mae)} evals, expected at least 2")
    _check(bool(np.all(np.isfinite(mae))), f"non-finite eval MAE {mae}")
    _check(mae[-1] < mae[0], f"eval MAE did not fall: {mae}")
    return {"iters": stats["iters"], "ticks": stats["ticks"],
            "devices": stats["devices"], "mae_first": mae[0],
            "mae_final": mae[-1]}, trace


def phase_b(sz: Sizes, mesh="auto", compare=True):
    """fedasync, fold_mode="auto" against "sequential"."""
    cfg_model, model = _model()
    cfg = WL.run_config(T=sz.cmp_iters, eval_every=sz.cmp_iters // 2,
                        window=sz.window, seed=0, fold_mode="auto")
    hist, trace, stats, tel = _engine_run("fedasync", model, cfg_model,
                                          sz.clients, sz.n_per, cfg,
                                          mesh=mesh)
    out = {"iters": stats["iters"], "devices": stats["devices"],
           "max_folds_per_tick": max(r.n_folds for r in tel.records),
           "mae_final": hist[-1].metrics["mae"]}
    if compare:
        _, seq, _, _ = _engine_run(
            "fedasync", model, cfg_model, sz.clients, sz.n_per,
            dataclasses.replace(cfg, fold_mode="sequential"), mesh=mesh)
        out["max_abs_err_vs_sequential"] = _max_err(trace, seq)
        # the auto rule at the deepest bucket this run folded: the
        # paper-width LSTM leaves must ride the compiled linear_scan kernel
        S = 1 << (out["max_folds_per_tick"] - 1).bit_length()
        w = model.init(jax.random.PRNGKey(0))
        b = jax.tree.map(lambda x: jnp.zeros((S,) + x.shape, x.dtype), w)
        _assert_kernel(lambda a, b, h0: fold_prefix(a, b, h0),
                       jnp.ones((S,), jnp.float32), b, w)
    return out, trace


def phase_c(sz: Sizes):
    """ASO-Fed with the feature_attention kernel against the jnp pass."""
    cfg_model, model = _model()
    cfg = WL.run_config(T=sz.cmp_iters, eval_every=sz.cmp_iters // 2,
                        window=sz.window, seed=0, feature_kernel=True)
    hist, kern, stats, _ = _engine_run("asofed", model, cfg_model,
                                       sz.clients, sz.n_per, cfg)
    _, ref, _, _ = _engine_run(
        "asofed", model, cfg_model, sz.clients, sz.n_per,
        dataclasses.replace(cfg, feature_kernel=False))
    w_x = model.init(jax.random.PRNGKey(0))["w_x"]
    _assert_kernel(lambda w: feature_attention(w, use_kernel=True), w_x)
    return {"iters": stats["iters"], "mae_final": hist[-1].metrics["mae"],
            "max_abs_err_vs_jnp": _max_err(kern, ref)}


def phase_d(sz: Sizes):
    """The engine against the per-arrival ASO-Fed oracle."""
    cfg_model, model = _model()
    cfg = WL.run_config(T=sz.ref_iters, eval_every=sz.ref_iters // 2,
                        seed=0)
    hist, trace, stats, _ = _engine_run("asofed", model, cfg_model,
                                        sz.ref_clients, sz.n_per, cfg)
    ref = run_asofed_reference(
        model, cfg_model, WL.make_clients(sz.ref_clients, n_per=sz.n_per),
        cfg)
    matched = [(t, w) for t, w in trace if t in ref]
    err = _max_err(matched, [(t, ref[t]) for t, _ in matched])
    return {"iters": stats["iters"], "trace_points": len(matched),
            "mae_final": hist[-1].metrics["mae"],
            "max_abs_err_vs_reference": err}


def sharded_vs_single(name, fn):
    """Run ``fn(mesh)`` over the ``data`` mesh of every device and on one
    device; returns the summary with the largest weight gap."""
    out, tr_sharded = fn("auto")
    _, tr_single = fn(None)
    _check(out["devices"] > 1, f"{name}: the run was not sharded")
    err = _max_err(tr_sharded, tr_single)
    _check(err < SHARDED_MAX_ERR,
           f"{name}: sharded vs single-device gap {err} >= {SHARDED_MAX_ERR}")
    out["max_abs_err_sharded_vs_single"] = err
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only phase (a) and fedasync, each sharded "
                         "over four chips and on one device")
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX finds "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    print(json.dumps({"smoke": "setup", "device_kind": dev.device_kind,
                      "devices": len(devices), "compile_cache": cache_dir}),
          flush=True)
    clock = CompileClock()
    sz = Sizes()
    if args.chips == 4:
        _phase("a_asofed_sharded", clock,
               lambda: sharded_vs_single("a", lambda m: phase_a(sz, m)))
        _phase("b_fedasync_sharded", clock,
               lambda: sharded_vs_single(
                   "b", lambda m: phase_b(sz, m, compare=False)))
    else:
        _phase("a_asofed_paper_width", clock, lambda: phase_a(sz)[0])
        _phase("b_fedasync_fold_auto", clock, lambda: phase_b(sz)[0])
        _phase("c_asofed_feature_kernel", clock, lambda: phase_c(sz))
        _phase("d_engine_vs_reference", clock, lambda: phase_d(sz))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
