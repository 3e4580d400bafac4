"""Plain reference of one benchmark job, written from the paper and the
configuration files alone: it imports nothing of ``repro``.  The model
(parameters, forward pass, loss) is the configuration family's module,
``families/<model.family>.py``; everything here serves every family.

It replays the job's event stream (per-client round delays drawn at pop
time from one seeded generator), each client's online data stream, the
local rounds (ASO-Fed Eq. 7-11, FedAsync's regularized SGD), the server
fold in arrival order (ASO-Fed Eq. 4 with the Eq. 5-6 feature pass,
FedAsync's staleness-weighted mixing) and the eval predictions.  Local
rounds of one *tick* (a run of arrivals from pairwise-distinct clients)
are independent of each other's folds, so they run vmapped over the tick;
the folds then run one arrival at a time.

Float32 runs at ``precision=highest``; ``dtype=bfloat16`` gives the
control (the same reference one precision step down).  ``fault`` plants
one of the faults the benchmark's check has to catch.
"""
from __future__ import annotations

import dataclasses
import heapq
import importlib.util
import math
import types
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = ("frozen_step", "half_batch", "altered_answer")


# ---------------------------------------------------------------------------
# Model: the configuration's family module, the seeded init
# ---------------------------------------------------------------------------

# where ``family`` looks for ``<family>.py``, in order
FAMILY_DIRS: List[Path] = [Path(__file__).resolve().parent / "families"]
_FAMILIES: Dict[Path, types.ModuleType] = {}


def family(model: dict) -> types.ModuleType:
    """The module of ``model["family"]``: ``<family>.py`` from the first
    of ``FAMILY_DIRS`` that holds one, loaded by file once."""
    name = model["family"]
    for d in FAMILY_DIRS:
        path = Path(d) / f"{name}.py"
        if path.is_file():
            break
    else:
        raise KeyError(f"no family module {name}.py in {FAMILY_DIRS}")
    if path not in _FAMILIES:
        spec = importlib.util.spec_from_file_location(
            f"chipbench_family_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _FAMILIES[path] = mod
    return _FAMILIES[path]


def param_shapes(model: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, init) of the configuration's parameters."""
    return family(model).param_shapes(model)


def init_params(model: dict, seed: int) -> Dict[str, jnp.ndarray]:
    """The configuration's seeded init: one key per parameter, split from
    ``PRNGKey(seed)`` in sorted-name order; ``fan_in`` leaves are standard
    normal over sqrt(fan in), ``zeros`` leaves are zero."""
    shapes = param_shapes(model)
    names = sorted(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(names))
    out = {}
    for name, key in zip(names, keys):
        shape, init = shapes[name]
        if init == "zeros":
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            out[name] = (jax.random.normal(key, shape, jnp.float32)
                         * (1.0 / math.sqrt(fan_in)))
    return out


def first_layer(model: dict) -> str:
    """The parameter the ASO-Fed feature pass reweights."""
    return family(model).first_layer(model)


def forward(model: dict, p, x):
    return family(model).forward(model, p, x)


def loss(model: dict, p, x, y):
    """Mean per-sample training loss of the configuration's task."""
    return family(model).loss(model, p, x, y)


def feature_pass(w):
    """ASO-Fed Eq. 5-6 with the row norm restored: each row of the first
    layer (trailing axis = columns) is reweighted by the softmax of its
    magnitudes, then rescaled to its former L2 norm."""
    shape = w.shape
    w2 = w.reshape(-1, shape[-1])
    a = jnp.abs(w2)
    e = jnp.exp(a - jnp.max(a, axis=-1, keepdims=True))
    out = e / jnp.sum(e, axis=-1, keepdims=True) * w2
    n_in = jnp.sqrt(jnp.sum(jnp.square(w2), axis=-1, keepdims=True))
    n_out = jnp.sqrt(jnp.sum(jnp.square(out), axis=-1, keepdims=True))
    return (out * (n_in / jnp.maximum(n_out, 1e-12))).reshape(shape)


# ---------------------------------------------------------------------------
# Event stream and online data streams
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Arrival:
    cid: int
    time: float
    delay: float


class EventStream:
    """Round completions in time order.  Each client's first round takes
    ``B / rate`` compute seconds, later rounds ``E * B / rate``, plus its
    network offset times a ``U[jitter]`` draw made when the previous round
    completes (the first rounds' draws in client order)."""

    def __init__(self, offsets: Sequence[float], seed: int, B: int, E: int,
                 rate: float, jitter: Sequence[float]):
        self.rng = np.random.default_rng(seed)
        self.offsets = list(offsets)
        self.jitter = tuple(jitter)
        self.round_compute = E * B / rate
        self.heap = []
        for cid, off in enumerate(self.offsets):
            first = B / rate + off * float(self.rng.uniform(*self.jitter))
            heapq.heappush(self.heap, (first, cid))

    def tick(self, limit: int) -> List[Arrival]:
        """The next arrivals, up to ``limit``, stopping before a client
        that already arrived in this tick."""
        out, seen = [], set()
        while len(out) < limit and self.heap and self.heap[0][1] not in seen:
            now, cid = heapq.heappop(self.heap)
            delay = self.round_compute + self.offsets[cid] * float(
                self.rng.uniform(*self.jitter))
            heapq.heappush(self.heap, (now + delay, cid))
            out.append(Arrival(cid, now, delay))
            seen.add(cid)
        return out


class DataStream:
    """A client's online training data: ``start_frac`` of the split visible
    at iteration 0, ``growth`` of it more per global iteration; minibatches
    are uniform draws with replacement over the visible prefix (short draws
    repeat)."""

    def __init__(self, x, y, seed: int, start_frac: float, growth: float):
        self.x, self.y = x, y
        self.n = len(x)
        self.start = max(1, int(start_frac * self.n))
        self.growth = growth
        self.rng = np.random.default_rng(seed)

    def visible(self, t: int) -> int:
        return min(self.n, self.start + int(self.growth * self.n * t))

    def batch(self, t: int, size: int):
        v = self.visible(t)
        idx = self.rng.integers(0, v, size=min(size, v))
        idx = np.resize(idx, size)
        return self.x[idx], self.y[idx]


# ---------------------------------------------------------------------------
# Local rounds and folds (one client / one arrival)
# ---------------------------------------------------------------------------


def _tree(f, *ts):
    """Leafwise ``f`` over parameter dicts, kept in the first one's dtypes
    (scalar coefficients are float32 even when the parameters are not)."""
    return {k: f(*(t[k] for t in ts)).astype(ts[0][k].dtype) for k in ts[0]}


def make_rules(model: dict, run: dict, fault: Optional[str]):
    """(init_client, local, fold, received_state) for the run's strategy.
    Counters, delays and mixing weights stay float32 in every dtype."""
    strategy = run["strategy"]
    eta = run["eta"]
    half = fault == "half_batch"

    def halve(x):
        return x[: x.shape[0] // 2] if half else x

    if strategy == "asofed":
        lam, beta = run["lam"], run["beta"]

        def init_client(w0):
            z = _tree(jnp.zeros_like, w0)
            s = jnp.zeros((), jnp.float32)
            return {"w": w0, "ws": w0, "h": z, "v": z, "dsum": s, "n": s}

        def local(st, xs, ys, delay, t):
            x = halve(xs.reshape((-1,) + xs.shape[2:]))
            y = halve(ys.reshape((-1,) + ys.shape[2:]))
            lval, g = jax.value_and_grad(
                lambda p: loss(model, p, x, y))(st["w"])
            g = _tree(lambda gi, wi, si: gi + lam * (wi - si),
                      g, st["w"], st["ws"])
            zeta = _tree(lambda gi, vi, hi: gi - vi + hi, g, st["v"], st["h"])
            if run["dynamic_lr"]:
                dbar = (st["dsum"] + delay) / jnp.maximum(st["n"] + 1, 1)
                r = jnp.maximum(1.0, jnp.log(jnp.maximum(dbar, 1e-6)))
            else:
                r = jnp.ones_like(delay)
            w_new = _tree(lambda wi, zi: wi - r * eta * zi, st["w"], zeta)
            st2 = dict(st, w=w_new,
                       h=_tree(lambda hi, vi: beta * hi + (1 - beta) * vi,
                               st["h"], st["v"]),
                       v=g, dsum=st["dsum"] + delay, n=st["n"] + 1)
            return st2, _tree(jnp.subtract, st["w"], w_new), lval

        def fold(server, up, cid, n_vis, t):
            counts = server["counts"].at[cid].set(n_vis)
            weight = n_vis / jnp.maximum(jnp.sum(counts), 1e-9)
            w = _tree(lambda wi, di: wi - weight * di, server["w"], up)
            if run["feature_learning"]:
                k = first_layer(model)
                w = dict(w, **{k: feature_pass(w[k])})
            return {"w": w, "counts": counts}, w

        def received_state(st, w, t):
            return dict(st, w=w, ws=w)

    elif strategy == "fedasync":
        alpha, rho = run["fedasync_alpha"], run["fedasync_staleness_exp"]
        mu = run["mu"]

        def init_client(w0):
            return {"w": w0, "version": jnp.zeros((), jnp.float32)}

        def local(st, xs, ys, delay, t):
            def step(p, xy):
                x, y = halve(xy[0]), halve(xy[1])
                lval, g = jax.value_and_grad(
                    lambda q: loss(model, q, x, y))(p)
                g = _tree(lambda gi, pi, ai: gi + mu * (pi - ai),
                          g, p, st["w"])
                return _tree(lambda pi, gi: pi - eta * gi, p, g), lval

            wk, ls = jax.lax.scan(step, st["w"], (xs, ys))
            return st, {"wk": wk, "version": st["version"]}, jnp.mean(ls)

        def fold(server, up, cid, n_vis, t):
            a = alpha * (1.0 + (t - up["version"])) ** (-rho)
            w = _tree(lambda wi, ki: (1 - a) * wi + a * ki,
                      server["w"], up["wk"])
            return {"w": w}, w

        def received_state(st, w, t):
            return {"w": w, "version": t + 1.0}

    else:
        raise ValueError(f"no reference for strategy {strategy!r}")
    return init_client, local, fold, received_state


# ---------------------------------------------------------------------------
# The job
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Replay:
    ticks: List[Tuple[int, int]]  # (global iteration after the tick, folds)
    tick_loss: List[float]  # cohort-mean train loss of each tick
    sim_time: float  # arrival time of the last fold
    preds: np.ndarray  # (test samples of all clients, outputs)


def _bucket(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def replay(model: dict, run: dict, datasets, offsets: Sequence[float], *,
           run_seed: int, stream_seed: int, stop: int,
           dtype=jnp.float32, fault: Optional[str] = None) -> Replay:
    """Run the job's first ``stop`` global iterations, with each tick's
    cohort-mean train loss, and predict every client's test split with the
    central model after them.

    A tick takes at most ``run["max_cohort"]`` arrivals (all ``K`` clients
    when it is unset), as the program's scheduler does.  With
    ``run["state_residency"] == "host"`` the client states live in host
    memory and only each tick's rows go to the device and back; otherwise
    they stay stacked on the device.  Both give the same answers."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        return _replay(model, run, datasets, offsets, run_seed, stream_seed,
                       stop, dtype, fault)


def _replay(model, run, datasets, offsets, run_seed, stream_seed, stop,
            dtype, fault):
    K = len(datasets)
    cap = run.get("max_cohort") or K
    host = run.get("state_residency") == "host"
    B, E = run["batch_size"], run["local_epochs"]
    init_client, local, fold, received_state = make_rules(model, run, fault)
    cast = lambda t: jax.tree.map(lambda v: v.astype(dtype), t)  # noqa: E731
    w0 = cast(init_params(model, run_seed))
    one = init_client(w0)
    # client states over K + 1 rows; padded tick slots use row K
    if host:
        states = jax.tree.map(
            lambda v: np.repeat(np.asarray(v)[None], K + 1, axis=0), one)
    else:
        states = jax.tree.map(
            lambda v: jnp.broadcast_to(v, (K + 1,) + v.shape), one)
    streams = [DataStream(d[0], d[1], stream_seed + i, run["start_frac"],
                          run["growth"]) for i, d in enumerate(datasets)]
    server = {"w": w0}
    if run["strategy"] == "asofed":
        server["counts"] = jnp.asarray(
            [s.visible(0) for s in streams] + [0], jnp.float32)
    events = EventStream(offsets, run_seed, B, E, run["compute_rate"],
                         run["jitter"])

    def rows(cohort, server, cids, xs, ys, delays, n_vis, ts, mask):
        """One tick on its clients' state rows: the local rounds vmapped,
        the folds in arrival order, the states the clients receive."""
        new, ups, losses = jax.vmap(local)(cohort, xs, ys, delays, ts)

        def step(sv, inp):
            up, cid, nv, t, m = inp
            sv2, w = fold(sv, up, cid, nv, t)
            sv2 = jax.tree.map(lambda a, b: jnp.where(m, a, b), sv2, sv)
            return sv2, w

        server, ws = jax.lax.scan(step, server, (ups, cids, n_vis, ts, mask))
        return jax.vmap(received_state)(new, ws, ts), server, losses

    def mean_loss(losses, mask):
        m = mask.astype(jnp.float32)
        return jnp.sum(losses.astype(jnp.float32) * m) / jnp.sum(m)

    @jax.jit
    def tick_fn(states, server, cids, xs, ys, delays, n_vis, ts, mask):
        cohort = jax.tree.map(lambda v: v[cids], states)
        new, server, losses = rows(cohort, server, cids, xs, ys, delays,
                                   n_vis, ts, mask)
        states = jax.tree.map(lambda s, v: s.at[cids].set(v), states, new)
        return states, server, mean_loss(losses, mask)

    @jax.jit
    def tick_rows_fn(cohort, server, cids, xs, ys, delays, n_vis, ts, mask):
        new, server, losses = rows(cohort, server, cids, xs, ys, delays,
                                   n_vis, ts, mask)
        return new, server, mean_loss(losses, mask)

    x_tmpl, y_tmpl = datasets[0][0], datasets[0][1]
    t, sim_time = 0, 0.0
    ticks, tick_loss = [], []
    while t < stop:
        arr = events.tick(min(cap, stop - t))
        P = _bucket(len(arr))
        cids = np.full(P, K, np.int32)
        xs = np.zeros((P, E, B) + x_tmpl.shape[1:], x_tmpl.dtype)
        ys = np.zeros((P, E, B) + y_tmpl.shape[1:], y_tmpl.dtype)
        delays = np.zeros(P, np.float32)
        n_vis = np.zeros(P, np.float32)
        ts = np.zeros(P, np.float32)
        mask = np.zeros(P, bool)
        for i, a in enumerate(arr):
            s = streams[a.cid]
            cids[i], delays[i], ts[i], mask[i] = a.cid, a.delay, t + i, True
            n_vis[i] = s.visible(t + i)
            for e in range(E):
                xs[i, e], ys[i, e] = s.batch(t + i, B)
        cast_x = xs.astype(dtype) if xs.dtype == np.float32 else xs
        cast_y = ys.astype(dtype) if ys.dtype == np.float32 else ys
        inputs = (jnp.asarray(cids), jnp.asarray(cast_x),
                  jnp.asarray(cast_y), jnp.asarray(delays),
                  jnp.asarray(n_vis), jnp.asarray(ts), jnp.asarray(mask))
        if host:
            cohort = jax.tree.map(lambda v: v[cids], states)
            new_rows, new_server, lval = tick_rows_fn(cohort, server, *inputs)
        else:
            new_states, new_server, lval = tick_fn(states, server, *inputs)
        if fault != "frozen_step":  # the fault: a step that changes nothing
            if host:
                # padded slots all write row K, with equal values
                for s_leaf, n_leaf in zip(jax.tree.leaves(states),
                                          jax.tree.leaves(new_rows)):
                    s_leaf[cids] = np.asarray(n_leaf)
            else:
                states = new_states
            server = new_server
        t += len(arr)
        sim_time = arr[-1].time
        ticks.append((t, len(arr)))
        tick_loss.append(lval)
    del states

    x_test = np.concatenate([d[2] for d in datasets])
    predict = jax.jit(lambda w, x: forward(
        model, w, x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x))
    pred_block = family(model).pred_block(model)
    preds = []
    for s in range(0, len(x_test), pred_block):
        blk = x_test[s: s + pred_block]
        n = len(blk)
        if n < pred_block:
            blk = np.concatenate(
                [blk, np.zeros((pred_block - n,) + blk.shape[1:], blk.dtype)])
        preds.append(np.asarray(predict(server["w"], jnp.asarray(blk)),
                                np.float32)[:n])
    preds = np.concatenate(preds)
    if fault == "altered_answer":
        preds[0] = -preds[0]
    return Replay(ticks=ticks, tick_loss=[float(v) for v in tick_loss],
                  sim_time=sim_time, preds=preds)
