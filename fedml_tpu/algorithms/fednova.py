"""FedNova — normalized averaging for heterogeneous local steps (Wang et al.).

Reference: ``simulation/sp/fednova`` / ``ml/trainer/fednova_trainer.py``
(normalized updates + tau; the FedNova branch of ``agg_operator.py`` passes
through pre-normalized updates).  Semantics:

  client i runs tau_i local steps; d_i = (x - y_i) / a_i
    plain SGD:      a_i = tau_i
    momentum rho:   a_i = (tau_i - rho(1-rho^tau_i)/(1-rho)) / (1-rho)
  server: x <- x - tau_eff * sum_i p_i d_i,  p_i = n_i/n,
          tau_eff = sum_i p_i a_i  (objective-consistent choice)

Heterogeneous tau_i is exactly what ``step_mode="match"`` produces on ragged
Dirichlet shards, so FedNova is the principled companion of the masked scan
(SURVEY.md §7 hard part 1).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core import pytree as pt
from ..fl.algorithm import FedAlgorithm
from ..fl.local_sgd import own_step_budget, split_variables
from ..fl.types import ClientOutput


class FedNova(FedAlgorithm):
    name = "FedNova"

    def client_update(self, global_variables, client_state, server_state, x, y, count, key, step_bound=None):
        new_vars, metrics = self._local_train(global_variables, x, y, count, key, None, step_bound)
        g_params, _ = split_variables(global_variables)
        l_params, l_rest = split_variables(new_vars)
        if self.hp.step_mode == "match":
            tau = own_step_budget(self.hp, count).astype(jnp.float32)
        else:
            tau = jnp.float32(self.hp.local_steps)
        rho = self.hp.momentum
        if rho:
            a_i = (tau - rho * (1.0 - rho**tau) / (1.0 - rho)) / (1.0 - rho)
        else:
            a_i = tau
        d_i = jax.tree_util.tree_map(lambda gx, ly: (gx - ly) / a_i, g_params, l_params)
        contribution = {"d": d_i, "a": a_i, "rest": l_rest}
        return ClientOutput(contribution=contribution, client_state=client_state, metrics=metrics)

    def aggregate(self, stacked, weights):
        d_bar = pt.tree_weighted_mean(stacked["d"], weights)  # sum p_i d_i
        w = weights / jnp.maximum(weights.sum(), 1e-12)
        tau_eff = jnp.sum(w * stacked["a"])  # sum p_i a_i
        rest = pt.tree_weighted_mean(stacked["rest"], weights)
        return {"d": d_bar, "tau_eff": tau_eff, "rest": rest}

    def server_update(self, global_variables, server_state, agg, round_idx):
        g_params, _ = split_variables(global_variables)
        scale = agg["tau_eff"] * self.hp.server_lr
        new_params = jax.tree_util.tree_map(lambda x, d: x - scale * d, g_params, agg["d"])
        return {"params": new_params, **agg["rest"]}, server_state
