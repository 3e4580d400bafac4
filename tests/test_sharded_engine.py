"""Sharded cohort engine vs single-device numerical equivalence.

Runs in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=4
(device count is locked at first jax init, so it cannot be set in-process).
Validates that the data-mesh path — stacked client state sharded over
``data``, shard_map'd vmapped local rounds, replicated fold scan — replays
the single-device trajectory (and hence the sequential per-arrival
reference) within fp32 tolerance, for asofed and fedasync (the latter
also on the associative fold's linear_scan kernel).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, dataclasses
    import jax
    import numpy as np
    from repro.configs import get_arch
    from repro.core.algorithms import get_strategy
    from repro.data import airquality_like
    from repro.models import LOCAL, build_model
    from repro.common.sharding import data_mesh
    from repro.sim.engine import RunConfig, run_strategy
    from repro.sim.profiles import make_sim_clients

    assert jax.device_count() == 4
    mesh = data_mesh()
    assert mesh is not None and mesh.devices.size == 4

    data = airquality_like(n_clients=6, n_per=40)
    cfg_model = dataclasses.replace(
        get_arch("paper-lstm"), in_features=8, out_features=1, hidden=8
    )
    model = build_model(cfg_model, LOCAL)
    cfg = RunConfig(T=24, batch_size=4, local_epochs=2, eta=0.02, lam=1.0,
                    beta=0.001, task="regression", eval_every=12, seed=0)

    # the associative fold on the linear_scan kernel, run replicated
    # inside an explicit SPMD region on the mesh
    assoc = dataclasses.replace(cfg, fold_mode="associative",
                                fold_kernel=True, fold_kernel_interpret=True)
    out = {}
    for name, alg, c in (("asofed", "asofed", cfg),
                         ("fedasync", "fedasync", cfg),
                         ("fedasync-assoc", "fedasync", assoc)):
        tr_sharded, tr_single = [], []
        run_strategy(get_strategy(alg), model, cfg_model,
                     make_sim_clients(data, seed=0), c,
                     trace=tr_sharded, mesh="auto")
        run_strategy(get_strategy(alg), model, cfg_model,
                     make_sim_clients(data, seed=0), c,
                     trace=tr_single, mesh=None)
        assert len(tr_sharded) == len(tr_single) >= 2, alg
        err = 0.0
        for (t1, w1), (t2, w2) in zip(tr_sharded, tr_single):
            assert t1 == t2
            for a, b in zip(jax.tree.leaves(w1), jax.tree.leaves(w2)):
                err = max(err, float(np.max(np.abs(a - b))))
        out[name] = {"ticks": len(tr_sharded), "max_err": err}
    print("RESULT" + json.dumps(out))
    """
)


@pytest.mark.slow
def test_sharded_engine_matches_single_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")]
    ).rstrip(os.pathsep)
    # four virtual CPU devices; a test child never claims a chip
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env,
        capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT")]
    assert line, proc.stdout
    out = json.loads(line[-1][len("RESULT"):])
    for alg, rec in out.items():
        # sharded local rounds only reassociate fp math
        assert rec["max_err"] < 3e-4, (alg, rec)
