"""FedDyn — dynamic regularization (Acar et al.).

Reference: ``simulation/sp/feddyn`` (the FedDyn branch of ``agg_operator.py``
sums client weights).  Semantics:

  local objective: f_i(w) - <lambda_i, w> + (alpha/2)||w - x||^2
  after training:  lambda_i <- lambda_i - alpha (y_i - x)
  server:          h <- h - alpha (|S|/N) mean_S(y_i - x)
                   x <- mean_S(y_i) - h / alpha

Client state = lambda_i (per-client linear term), server state = h.
Both live as stacked device pytrees; the extra loss terms are a pure
``loss_extra`` hook over the shared scan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core import pytree as pt
from ..fl.algorithm import FedAlgorithm
from ..fl.local_sgd import split_variables
from ..fl.types import ClientOutput


class FedDyn(FedAlgorithm):
    name = "FedDyn"

    def loss_extra(self):
        alpha = self.hp.feddyn_alpha

        def extra(params, ctx):
            global_params, lam = ctx
            lin = pt.tree_dot(lam, params)
            prox = 0.5 * alpha * pt.tree_sq_norm(pt.tree_sub(params, global_params))
            return prox - lin

        return extra

    def init_server_state(self, variables):
        return pt.tree_zeros_like(variables["params"])

    def init_client_state(self, variables):
        return pt.tree_zeros_like(variables["params"])

    def make_ctx(self, global_variables, client_state, server_state):
        return (global_variables["params"], client_state)

    def client_update(self, global_variables, client_state, server_state, x, y, count, key, step_bound=None):
        ctx = self.make_ctx(global_variables, client_state, server_state)
        new_vars, metrics = self._local_train(global_variables, x, y, count, key, ctx, step_bound)
        g_params, _ = split_variables(global_variables)
        l_params, l_rest = split_variables(new_vars)
        alpha = self.hp.feddyn_alpha
        delta = pt.tree_sub(l_params, g_params)
        new_lam = pt.tree_axpy(-alpha, delta, client_state)
        contribution = {"variables": {"params": l_params, **l_rest}, "delta": delta}
        return ClientOutput(contribution=contribution, client_state=new_lam, metrics=metrics)

    def aggregate(self, stacked, weights):
        uni = jnp.ones_like(weights)  # FedDyn uses uniform client means
        return {
            "variables": pt.tree_weighted_mean(stacked["variables"], uni),
            "delta": pt.tree_weighted_mean(stacked["delta"], uni),
        }

    def server_update(self, global_variables, server_state, agg, round_idx):
        alpha = self.hp.feddyn_alpha
        frac = (self.cfg.client_num_per_round / self.cfg.client_num_in_total) if self.cfg else 1.0
        new_h = pt.tree_axpy(-alpha * frac, agg["delta"], server_state)
        a_params, a_rest = split_variables(agg["variables"])
        new_params = jax.tree_util.tree_map(lambda a, h: a - h / alpha, a_params, new_h)
        return {"params": new_params, **a_rest}, new_h
