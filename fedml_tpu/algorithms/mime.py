"""MimeLite — server momentum applied inside local steps (Karimireddy et al.).

Reference: ``simulation/sp/mime`` (Mime branch of ``agg_operator.py`` averages
params and grads).  MimeLite semantics:

  local step uses the server momentum m (frozen during the round):
      d = (1 - beta) * g(y) + beta * m ;  y <- y - lr * d
  clients also report grad f_i(x) (full-batch at the global point)
  server:  x <- mean_S(y_i);  m <- (1 - beta) * mean_S(grad f_i(x)) + beta * m

Server state = m.  The momentum mix is a ``grad_hook``; the full-batch
gradient reuses ``make_full_grad_fn``'s batched scan.
"""

from __future__ import annotations

import jax

from ..core import pytree as pt
from ..fl.algorithm import FedAlgorithm
from ..fl.local_sgd import make_full_grad_fn
from ..fl.types import ClientOutput


class Mime(FedAlgorithm):
    name = "Mime"

    def build(self, model):
        super().build(model)
        self._full_grad = make_full_grad_fn(model, self.hp)
        return self

    def grad_hook(self):
        beta = self.hp.mime_momentum

        def mix(grads, ctx):
            m = ctx
            return jax.tree_util.tree_map(lambda g, mi: (1 - beta) * g + beta * mi, grads, m)

        return mix

    def init_server_state(self, variables):
        return pt.tree_zeros_like(variables["params"])

    def make_ctx(self, global_variables, client_state, server_state):
        return server_state

    def client_update(self, global_variables, client_state, server_state, x, y, count, key, step_bound=None):
        ctx = self.make_ctx(global_variables, client_state, server_state)
        new_vars, metrics = self._local_train(global_variables, x, y, count, key, ctx, step_bound)
        gkey = jax.random.fold_in(key, 0x6D696D65)
        fg = self._full_grad(global_variables, x, y, count, gkey)
        return ClientOutput(
            contribution={"variables": new_vars, "full_grad": fg},
            client_state=client_state, metrics=metrics,
        )

    def aggregate(self, stacked, weights):
        return {
            "variables": pt.tree_weighted_mean(stacked["variables"], weights),
            "full_grad": pt.tree_weighted_mean(stacked["full_grad"], weights),
        }

    def server_update(self, global_variables, server_state, agg, round_idx):
        beta = self.hp.mime_momentum
        new_m = jax.tree_util.tree_map(
            lambda g, m: (1 - beta) * g + beta * m, agg["full_grad"], server_state
        )
        return agg["variables"], new_m
