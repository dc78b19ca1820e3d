"""Shared FL types.

Replaces the reference's duck-typed ``args`` threading and the
``Params``/``Context`` kwargs bags (``core/alg_frame/params.py``,
``context.py``) with small typed containers that are jit-friendly
(pytrees of arrays) or static (frozen dataclasses hashed into the trace).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax


@dataclass(frozen=True)
class HParams:
    """Static (trace-time) hyperparameters of the local problem.

    One frozen dataclass instead of ``hasattr`` probing on ``args``
    (reference ``ml/trainer/my_model_trainer_classification.py:21-60``).
    """

    epochs: int = 1
    batch_size: int = 32
    learning_rate: float = 0.03
    momentum: float = 0.0
    weight_decay: float = 0.0
    client_optimizer: str = "sgd"
    server_optimizer: str = "sgd"
    server_lr: float = 1.0
    server_momentum: float = 0.0
    # algorithm knobs (see Config for provenance)
    fedprox_mu: float = 0.0
    feddyn_alpha: float = 0.01
    mime_momentum: float = 0.9
    steps_per_epoch: int = 0  # static: ceil(capacity / batch_size)
    step_mode: str = "match"  # match reference per-client step counts | fixed
    compute_dtype: str = "float32"
    loss: str = "cross_entropy"

    @property
    def local_steps(self) -> int:
        return self.epochs * self.steps_per_epoch


class ClientOutput:
    """What a client sends up: its contribution (pytree — full weights for
    FedAvg-family, grads for FedSGD, tuples for SCAFFOLD), refreshed persistent
    client state, and local metrics.  Registered as a pytree so it can flow
    through vmap/scan."""

    def __init__(self, contribution: Any, client_state: Any, metrics: dict):
        self.contribution = contribution
        self.client_state = client_state
        self.metrics = metrics

    def tree_flatten(self):
        return (self.contribution, self.client_state, self.metrics), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    ClientOutput,
    lambda co: co.tree_flatten(),
    lambda aux, children: ClientOutput.tree_unflatten(aux, children),
)
