"""FedAlgorithm — the pure-function frame replacing ClientTrainer/ServerAggregator.

The reference couples algorithm logic to its actor runtime: ``ClientTrainer``
(``core/alg_frame/client_trainer.py:10``) mutates a model in-place on a worker
process, and ``ServerAggregator`` + ``FedMLAggOperator.agg``
(``core/alg_frame/server_aggregator.py:14``, ``ml/aggregator/agg_operator.py:9``)
branch per optimizer on lists of state_dicts.  Here an algorithm is five pure
methods over pytrees — everything composes with jit/vmap/shard_map and runs
identically on the sequential SP backend and the sharded MESH backend:

- ``init_server_state``  (server optimizer state, control variates, momentum)
- ``init_client_state``  (per-client persistent state; stacked over clients)
- ``client_update``      (local training -> ClientOutput.contribution)
- ``aggregate``          (stacked contributions + weights -> aggregate)
- ``server_update``      (aggregate -> new global variables)

Defaults implement FedAvg: sample-weighted mean of full client weights
(the exact math of ``fedavg_api.py:144-159`` / ``agg_operator.py`` "FedAvg"
branch) and identity server step.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import optax

from ..core import pytree as pt
from .local_sgd import make_local_train_fn, split_variables
from .types import ClientOutput, HParams


class FedAlgorithm:
    name = "FedAvg"
    #: ``client_update`` trains through ``local_train``'s step loop and hands it
    #: ``step_bound``; the round engine's lane buckets are for such algorithms
    has_step_loop = True

    def __init__(self, hp: HParams, cfg=None):
        self.hp = hp
        self.cfg = cfg
        self._local_train = None

    # -- build ---------------------------------------------------------------
    def build(self, model) -> "FedAlgorithm":
        """Close over the model to build the jit-able local train fn."""
        self._local_train = make_local_train_fn(
            model, self.hp, loss_extra=self.loss_extra(), grad_hook=self.grad_hook()
        )
        return self

    def loss_extra(self):
        return None

    def grad_hook(self):
        return None

    # -- state ---------------------------------------------------------------
    def init_server_state(self, variables: dict) -> Any:
        return ()

    def init_client_state(self, variables: dict) -> Optional[Any]:
        return None

    # -- client side -----------------------------------------------------------
    def make_ctx(self, global_variables: dict, client_state, server_state):
        """Context pytree passed to loss/grad hooks during local training."""
        return None

    def client_update(self, global_variables, client_state, server_state, x, y, count, key, step_bound=None) -> ClientOutput:
        ctx = self.make_ctx(global_variables, client_state, server_state)
        new_vars, metrics = self._local_train(global_variables, x, y, count, key, ctx, step_bound)
        return ClientOutput(contribution=new_vars, client_state=client_state, metrics=metrics)

    # -- server side -----------------------------------------------------------
    def supports_associative_fold(self) -> bool:
        """True when ``aggregate`` is a weight-associative fold: the result
        of ``aggregate(stacked, weights)`` equals folding one ``(update,
        weight)`` at a time into a running weighted sum and dividing at the
        end, in any arrival order.  The stock sample-weighted mean is; this
        is the capability gate for the cross-silo streaming accumulator and
        the buffered-async server (``FedMLAggregator.fold``), which would
        silently compute the wrong thing for an order- or set-sensitive
        ``aggregate`` (trimmed means, coordinate medians, Krum...).  The
        SAME declaration gates the secure-aggregation protocols (ISSUE 15):
        pairwise-mask SecAgg is a mod-field SUM — associative by
        construction — so masked uploads ride a field-domain sibling of the
        f32 fold (``parallel.stream_fold.FieldStreamAccumulator``), and an
        algorithm that cannot fold cannot be secure-aggregated either.  A
        subclass that overrides ``aggregate`` with another associative form
        may opt back in by overriding this to True."""
        return type(self).aggregate is FedAlgorithm.aggregate

    def aggregate(self, stacked_contributions, weights: jax.Array):
        return pt.tree_weighted_mean(stacked_contributions, weights)

    def server_update(self, global_variables, server_state, agg, round_idx):
        return agg, server_state


def config_supports_associative_fold(cfg) -> bool:
    """Whether ``cfg``'s algorithm declares its aggregate weight-associative
    — the config-level form of :meth:`FedAlgorithm.supports_associative_
    fold`, used by the secure-aggregation gates (``cross_silo/secagg_*``)
    before any model exists."""
    from ..algorithms import create as create_algorithm, hparams_from_config

    algo = create_algorithm(cfg, hparams_from_config(cfg, steps_per_epoch=1))
    return bool(algo.supports_associative_fold())


def make_server_optimizer(hp: HParams) -> optax.GradientTransformation:
    """Server-side optimizer for the FedOpt family (reference
    ``sp/fedopt/optrepo.py`` torch-optimizer lookup)."""
    if hp.server_optimizer == "sgd":
        return optax.sgd(hp.server_lr, momentum=hp.server_momentum or None)
    if hp.server_optimizer == "adam":
        return optax.adam(hp.server_lr, b1=0.9, b2=0.99, eps=1e-3)
    if hp.server_optimizer == "adagrad":
        return optax.adagrad(hp.server_lr)
    if hp.server_optimizer == "yogi":
        # FedYogi (Reddi et al.) — adaptive server optimizer
        return optax.yogi(hp.server_lr)
    raise ValueError(f"unknown server optimizer {hp.server_optimizer!r}")
