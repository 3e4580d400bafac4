"""Federated LLM training driver.

Runs the ASO-Fed protocol over K clients whose local data are non-IID
synthetic token streams; each client's local step and the server's Eq.(4)
fold + Eq.(5)-(6) feature pass are jitted (and pjit over a mesh when one is
requested).  On this CPU container it runs reduced configs end-to-end; on a
real TPU fleet the same code runs full configs (the dry-run proves the
lowering).

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
        --clients 4 --steps 40 --seq 128 --batch 8
"""
from __future__ import annotations

import argparse
import dataclasses
import heapq
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.common.compile_cache import enable_compile_cache
from repro.configs import get_arch
from repro.core.feature_learning import apply_feature_learning
from repro.data.lm import batches_from_tokens, federated_token_clients
from repro.launch.mesh import make_local_mesh
from repro.models import build_model, make_dist, LOCAL
from repro.optim.asofed import asofed_transform, init_slots


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--steps", type=int, default=40, help="global iterations")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--eta", type=float, default=3e-3)
    ap.add_argument("--lam", type=float, default=0.1)
    ap.add_argument("--beta", type=float, default=0.001)
    ap.add_argument("--no-feature-learning", action="store_true")
    ap.add_argument("--mesh", action="store_true", help="use all local devices")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    dist = (
        make_dist(cfg, make_local_mesh(), remat="none")
        if args.mesh
        else LOCAL
    )
    model = build_model(cfg, dist)
    key = jax.random.PRNGKey(args.seed)
    print(f"arch={cfg.name} reduced={args.reduced} vocab={cfg.vocab_size} "
          f"d={cfg.d_model} L={cfg.n_layers}")

    # --- federated state ------------------------------------------------
    w_server = model.init(key, jnp.float32)
    n_params = sum(int(x.size) for x in jax.tree.leaves(w_server))
    print(f"params: {n_params/1e6:.2f}M")
    streams = federated_token_clients(
        args.clients, cfg.vocab_size, tokens_per_client=200_000, seed=args.seed
    )
    iters = [
        batches_from_tokens(s, args.batch, args.seq, seed=i)
        for i, s in enumerate(streams)
    ]
    rng = np.random.default_rng(args.seed)
    delays = rng.uniform(10.0, 100.0, size=args.clients)  # paper's offsets

    client_params = [jax.tree.map(jnp.copy, w_server) for _ in range(args.clients)]
    client_server_copy = [w_server for _ in range(args.clients)]
    slots = [init_slots(w_server) for _ in range(args.clients)]
    n_k = np.full(args.clients, 1.0)

    @jax.jit
    def local_step(params, server_params, sl, batch, delay):
        def loss_of(p):
            l, m = model.loss(p, batch)
            return l, m

        (loss, metrics), grads = jax.value_and_grad(loss_of, has_aux=True)(params)
        updates, new_slots = asofed_transform(
            grads, sl, params, server_params,
            lam=args.lam, beta=args.beta, eta=args.eta, delay=delay,
        )
        new_params = jax.tree.map(
            lambda p, u: (p.astype(jnp.float32) + u).astype(p.dtype),
            params, updates,
        )
        return new_params, new_slots, loss

    @jax.jit
    def server_fold(w, delta, weight):
        return jax.tree.map(
            lambda a, d: a - weight * d.astype(a.dtype), w, delta
        )

    # --- event-driven async loop ----------------------------------------
    heap = [(float(delays[k]), k) for k in range(args.clients)]
    heapq.heapify(heap)
    t0 = time.perf_counter()
    losses = []
    for it in range(1, args.steps + 1):
        now, k = heapq.heappop(heap)
        batch = {kk: jnp.asarray(v) for kk, v in next(iters[k]).items()}
        before = client_params[k]
        new_p, slots[k], loss = local_step(
            before, client_server_copy[k], slots[k], batch, jnp.float32(delays[k])
        )
        delta = jax.tree.map(lambda a, b: a - b, before, new_p)
        n_k[k] += args.batch * args.seq
        weight = n_k[k] / n_k.sum()
        w_server = server_fold(w_server, delta, jnp.float32(weight))
        if not args.no_feature_learning:
            w_server = apply_feature_learning(w_server, cfg)
        # client pulls the fresh central model
        client_params[k] = jax.tree.map(jnp.copy, w_server)
        client_server_copy[k] = w_server
        heapq.heappush(heap, (now + float(delays[k]), k))
        losses.append(float(loss))
        if it % 10 == 0 or it == 1:
            print(f"iter {it:4d} client {k} loss {np.mean(losses[-10:]):.4f} "
                  f"sim_t {now:8.1f}s wall {time.perf_counter()-t0:6.1f}s",
                  flush=True)

    if args.checkpoint:
        save_checkpoint(args.checkpoint, w_server, step=args.steps)
        print("saved checkpoint to", args.checkpoint)
    print(json.dumps({"final_loss_avg10": float(np.mean(losses[-10:])),
                      "first_loss": losses[0]}))


if __name__ == "__main__":
    main()
