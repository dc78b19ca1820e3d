"""FedSGD — one full-batch gradient per round, with optional compression.

Reference: ``sp_fedsgd_cifar10_resnet20_example`` recipe (BASELINE.md) — each
client reports grad f_i(x); the server takes the sample-weighted mean and does
one SGD step.  Compression (``topk | eftopk | quantize | qsgd``,
``ml/utils/compression.py``) applies per client on the flat gradient; EF-TopK
residuals are the per-client persistent state (explicit, device-resident),
replacing the reference's stateful host-side ``EFTopKCompressor`` object.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core import pytree as pt
from ..fl.algorithm import FedAlgorithm, make_server_optimizer
from ..fl.local_sgd import make_full_grad_fn, split_variables
from ..fl.types import ClientOutput
from ..ops import compression as comp


class FedSGD(FedAlgorithm):
    name = "FedSGD"
    has_step_loop = False  # one full-shard gradient, no local steps

    def __init__(self, hp, cfg=None):
        super().__init__(hp, cfg)
        self._server_opt = make_server_optimizer(hp)
        self.compression = getattr(cfg, "compression", "no") if cfg else "no"
        self.ratio = getattr(cfg, "compression_ratio", 0.01) if cfg else 0.01
        self.qlevel = getattr(cfg, "quantize_level", 8) if cfg else 8

    def build(self, model):
        super().build(model)
        self._full_grad = make_full_grad_fn(model, self.hp)
        return self

    def init_server_state(self, variables):
        return self._server_opt.init(variables["params"])

    def init_client_state(self, variables):
        if self.compression == "eftopk":
            flat, _ = pt.tree_flatten_to_vector(variables["params"])
            return jnp.zeros_like(flat)
        return None

    def client_update(self, global_variables, client_state, server_state, x, y, count, key):
        grad = self._full_grad(global_variables, x, y, count, key)
        new_state = client_state
        if self.compression != "no":
            flat, unravel = pt.tree_flatten_to_vector(grad)
            flat, new_state = comp.compress(
                self.compression, flat, key=jax.random.fold_in(key, 7),
                residual=client_state, ratio=self.ratio, quantize_level=self.qlevel,
            )
            grad = unravel(flat)
        metrics = {
            "train_loss": jnp.float32(0.0),
            "num_steps": jnp.float32(1.0),
            "num_samples": count.astype(jnp.float32),
        }
        return ClientOutput(contribution=grad, client_state=new_state, metrics=metrics)

    def server_update(self, global_variables, server_state, agg, round_idx):
        g_params, g_rest = split_variables(global_variables)
        updates, new_state = self._server_opt.update(agg, server_state, g_params)
        import optax

        new_params = optax.apply_updates(g_params, updates)
        return {"params": new_params, **g_rest}, new_state
