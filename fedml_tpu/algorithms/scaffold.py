"""SCAFFOLD — control-variate corrected local SGD (Karimireddy et al.).

Reference: ``simulation/sp/scaffold`` and the SCAFFOLD branch of
``agg_operator.py`` (averages both params and control variates — the
"3-tuple agg" of SURVEY.md §2.3).  Semantics (option II of the paper):

  local step:   y <- y - lr * (g(y) - c_i + c)
  after K steps: c_i+ = c_i - c + (x - y) / (K * lr)
  server:       x <- x + lr_s * mean_S(y - x);  c <- c + (|S|/N) * mean_S(c_i+ - c_i)

Client state = c_i (pytree like params, stacked over all N clients, resident
on device).  Server state = c.  The gradient correction is a ``grad_hook``;
everything else reuses the shared local-SGD scan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core import pytree as pt
from ..fl.algorithm import FedAlgorithm
from ..fl.local_sgd import own_step_budget, split_variables
from ..fl.types import ClientOutput


class Scaffold(FedAlgorithm):
    name = "SCAFFOLD"

    def grad_hook(self):
        def correct(grads, ctx):
            _, c_global, c_i = ctx
            return jax.tree_util.tree_map(lambda g, c, ci: g + c - ci, grads, c_global, c_i)

        return correct

    def init_server_state(self, variables):
        return pt.tree_zeros_like(variables["params"])

    def init_client_state(self, variables):
        return pt.tree_zeros_like(variables["params"])

    def make_ctx(self, global_variables, client_state, server_state):
        return (global_variables["params"], server_state, client_state)

    def client_update(self, global_variables, client_state, server_state, x, y, count, key, step_bound=None):
        ctx = self.make_ctx(global_variables, client_state, server_state)
        new_vars, metrics = self._local_train(global_variables, x, y, count, key, ctx, step_bound)
        g_params, _ = split_variables(global_variables)
        l_params, l_rest = split_variables(new_vars)
        if self.hp.step_mode == "match":
            k_steps = own_step_budget(self.hp, count)
        else:
            k_steps = jnp.int32(self.hp.local_steps)
        inv_klr = 1.0 / (k_steps.astype(jnp.float32) * self.hp.learning_rate)
        # c_i+ = c_i - c + (x - y)/(K lr)
        new_ci = jax.tree_util.tree_map(
            lambda ci, c, gx, ly: ci - c + (gx - ly) * inv_klr,
            client_state, server_state, g_params, l_params,
        )
        delta_c = pt.tree_sub(new_ci, client_state)
        contribution = {"variables": {"params": l_params, **l_rest}, "delta_c": delta_c}
        return ClientOutput(contribution=contribution, client_state=new_ci, metrics=metrics)

    def aggregate(self, stacked, weights):
        # params sample-weighted (reference SCAFFOLD branch averages both);
        # delta_c uniformly (paper: 1/|S| sum)
        agg_vars = pt.tree_weighted_mean(stacked["variables"], weights)
        uni = jnp.ones_like(weights)
        agg_dc = pt.tree_weighted_mean(stacked["delta_c"], uni)
        return {"variables": agg_vars, "delta_c": agg_dc}

    def server_update(self, global_variables, server_state, agg, round_idx):
        g_params, _ = split_variables(global_variables)
        a_params, a_rest = split_variables(agg["variables"])
        lr_s = self.hp.server_lr
        new_params = jax.tree_util.tree_map(lambda x, a: x + lr_s * (a - x), g_params, a_params)
        frac = (self.cfg.client_num_per_round / self.cfg.client_num_in_total) if self.cfg else 1.0
        new_c = pt.tree_axpy(frac, agg["delta_c"], server_state)
        return {"params": new_params, **a_rest}, new_c
