"""FedAsync (Xie et al. 2019) as a cohort-engine strategy.

Local rule: regularized SGD from the client's stale model copy.  Fold
rule: staleness-weighted mixing ``w <- (1-a_t) w + a_t w_k`` with
``a_t = alpha * (1 + staleness)^(-rho)``, applied in arrival order; the
client then downloads the post-fold model and records its version.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common.pytree import tree_sub
from repro.core.algorithms.common import (bcast_rows, bool_tree,
                                          make_state_codec, sgd_epochs)
from repro.sim.engine import Strategy


class FedAsyncStrategy(Strategy):
    name = "fedasync"
    schedule = "async"

    def init_client(self, model, cfg, w0, client):
        return {"w": w0, "version": jnp.zeros((), jnp.float32)}

    def build_init_client(self, model, cfg):
        # batched stacked init: one vmapped jit instead of K+1 eager calls
        return lambda w0, n0: {"w": w0, "version": jnp.zeros((), jnp.float32)}

    def state_codec(self, model, cfg, w0):
        # stale model copies stored as reduced-dtype deltas from w0; the
        # version counter passes through fp32 (it counts global iters)
        return make_state_codec(
            cfg,
            anchor={"w": w0, "version": jnp.zeros((), jnp.float32)},
            mask={"w": bool_tree(w0, True), "version": False},
        )

    def upload_codec_view(self, model, cfg):
        # the wire delta is the local progress wk - w_stale (the client's
        # pre-round copy is the model the server already knows about from
        # its last download); the version stamp passes through untouched
        def extract(up, c0, bcast):
            return tree_sub(up["wk"], c0["w"])

        def rebuild(up, d, c0, bcast):
            return {"wk": jax.tree.map(jnp.add, c0["w"], d),
                    "version": up["version"]}

        return extract, rebuild

    def init_server(self, model, cfg_model, cfg, w0, clients, active):
        return {"w": w0}

    def build_local(self, model, cfg):
        sgd = sgd_epochs(model, cfg, mu=0.005)  # FedAsync regularized step

        def local(c, bcast, xs, ys, delay, n_vis, t_arr):
            wk, loss = sgd(c["w"], c["w"], xs, ys)
            return (c, {"wk": wk, "version": c["version"]},
                    {"train_loss": loss})

        return local

    def build_fold(self, model, cfg_model, cfg):
        def fold(server, up, idx, n_vis, t_arr):
            staleness = t_arr - up["version"]
            alpha_t = cfg.fedasync_alpha * (1.0 + staleness) ** (
                -cfg.fedasync_staleness_exp
            )
            w = jax.tree.map(lambda a, b: (1 - alpha_t) * a + alpha_t * b,
                             server["w"], up["wk"])
            return {"w": w}, {"w": w, "version": t_arr + 1.0}

        return fold

    def build_fold_affine(self, model, cfg_model, cfg):
        # the fold is exactly affine in the server weights:
        # w_s = (1 - a_s) w_{s-1} + a_s wk_s, so a = 1 - a_t, b = a_t wk.
        def carrier(server):
            return server["w"]

        def coeffs(server, up, idx, n_vis, t_arr, mask):
            staleness = t_arr - up["version"]
            alpha_t = cfg.fedasync_alpha * (1.0 + staleness) ** (
                -cfg.fedasync_staleness_exp
            )
            alpha_t = jnp.where(mask, alpha_t, 0.0)  # padded slot: identity
            b = jax.tree.map(lambda wk: bcast_rows(alpha_t, wk) * wk,
                             up["wk"])
            return 1.0 - alpha_t, b, None

        def unfold(server, h, aux, up, idx, n_vis, t_arr, mask):
            return ({"w": jax.tree.map(lambda x: x[-1], h)},
                    {"w": h, "version": t_arr + 1.0})

        return carrier, coeffs, unfold

    def build_merge(self, model, cfg):
        return lambda c, received: received
