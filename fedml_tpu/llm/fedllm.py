"""FedLLM — federated LoRA fine-tuning.

Parity with ``spotlight_prj/fedllm`` (``run_fedllm.py:47``,
``src/fedllm_trainer.py``): each silo fine-tunes LoRA adapters on its local
corpus; only the adapter tree (PEFT state-dict equivalent) crosses the
network; the server sample-weight-averages adapters.  The base model stays
frozen and device-resident — a round moves O(rank * d * layers) floats, not
the model.

The local step trains adapters through ``merge(base, lora)`` (see
``llm/lora.py``); the whole client update is one jitted scan, and adapter
averaging is the same ``tree_weighted_mean`` as every other algorithm.
"""

from __future__ import annotations

import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..arguments import Config
from ..core import pytree as pt, rng
from ..core.flags import cfg_extra
from ..models.transformer import Transformer, TransformerConfig
from ..obs.metrics import MetricsLogger
from . import lora as lora_lib


from ..core.checkpoint import RoundCheckpointMixin


class FedLLMSimulator(RoundCheckpointMixin):
    """Federated LoRA over token-sequence clients.

    dataset: FederatedDataset whose train_x are token sequences (b, T) and
    train_y the shifted targets (see data.loader text path).
    """

    def __init__(self, cfg: Config, dataset, tcfg: Optional[TransformerConfig] = None):
        self.cfg = cfg
        self.dataset = dataset
        self.rank = int(cfg_extra(cfg, "lora_r", 8))
        self.alpha = float(cfg_extra(cfg, "lora_alpha"))
        self.tcfg = tcfg or TransformerConfig.tiny(vocab_size=dataset.class_num)
        self.model = Transformer(self.tcfg)
        k0 = rng.root_key(cfg.random_seed)
        sample = jnp.zeros((cfg.batch_size, dataset.train_x.shape[1]), jnp.int32)
        self.base_params = self.model.init({"params": jax.random.fold_in(k0, 1)}, sample)["params"]
        self.global_lora = lora_lib.init_lora(
            self.base_params, self.rank, jax.random.fold_in(k0, 2),
            targets=cfg_extra(cfg, "lora_targets", lora_lib.DEFAULT_TARGETS),
        )
        self.root_key = k0
        self.round_idx = 0
        self.logger = MetricsLogger(cfg.metrics_jsonl_path or None)
        self._client_step = jax.jit(self._make_client_step())
        self._eval = jax.jit(self._eval_loss)

    def _make_client_step(self):
        cfg = self.cfg
        model = self.model
        alpha = self.alpha
        opt = optax.adamw(cfg.learning_rate)

        def loss_fn(lora, base, x, y):
            params = lora_lib.merge(base, lora, alpha=alpha)
            logits = model.apply({"params": params}, x, train=True)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), y
            ).mean()

        grad_fn = jax.value_and_grad(loss_fn)

        # one static step budget for all clients (shards are padded to a
        # common capacity, so there is exactly ONE compilation, not one per
        # distinct shard size); batches sample uniformly over the true count
        counts = self.dataset.local_sample_counts()
        self._capacity = int(counts.max())
        steps = cfg.epochs * max(1, self._capacity // cfg.batch_size)

        # the frozen base is an argument: closed over, its weights would be
        # constants baked into the compiled program
        def client_step(lora, base, x, y, count, key):
            opt_state = opt.init(lora)

            def step(carry, s):
                lora, opt_state = carry
                idx = jax.random.randint(
                    jax.random.fold_in(key, s), (cfg.batch_size,), 0, count
                )
                loss, g = grad_fn(lora, base, jnp.take(x, idx, 0), jnp.take(y, idx, 0))
                u, opt_state = opt.update(g, opt_state, lora)
                return (optax.apply_updates(lora, u), opt_state), loss

            (lora, _), losses = jax.lax.scan(step, (lora, opt_state), jnp.arange(steps))
            return lora, jnp.mean(losses)

        return client_step

    def _eval_loss(self, lora, base, x, y):
        params = lora_lib.merge(base, lora, alpha=self.alpha)
        logits = self.model.apply({"params": params}, x, train=False)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), y
        ).mean()
        return {"test_loss": loss, "test_ppl": jnp.exp(loss)}

    def run_round(self) -> dict:
        cfg = self.cfg
        ds = self.dataset
        n_total = ds.n_clients
        m = min(cfg.client_num_per_round, n_total)
        sampled = np.asarray(rng.sample_clients(self.root_key, self.round_idx, n_total, m))
        rkey = rng.round_key(self.root_key, self.round_idx)
        loras, weights, losses = [], [], []
        for ci in sampled:
            ix = ds.client_idx[int(ci)]
            reps = np.resize(ix, self._capacity)  # pad to the shared capacity
            x = jnp.asarray(ds.train_x[reps])
            y = jnp.asarray(ds.train_y[reps])
            new_lora, loss = self._client_step(
                self.global_lora, self.base_params, x, y, jnp.int32(len(ix)),
                rng.client_key(rkey, int(ci))
            )
            loras.append(new_lora)
            weights.append(float(len(ix)))
            losses.append(float(loss))
        stacked = pt.tree_stack(loras)
        self.global_lora = pt.tree_weighted_mean(stacked, jnp.asarray(weights))
        self.round_idx += 1
        return {"train_loss": float(np.mean(losses))}

    def evaluate(self, max_samples: int = 256) -> dict:
        ds = self.dataset
        x = jnp.asarray(ds.test_x[:max_samples])
        y = jnp.asarray(ds.test_y[:max_samples])
        return {k: float(v) for k, v in self._eval(self.global_lora, self.base_params, x, y).items()}

    # -- round-level checkpoint/resume (reference FedLLM PauseResumeCallback,
    # spotlight_prj/fedllm/src/trainer_callback.py: each FL round resumes the
    # trainer at a step offset; here the adapter tree + RNG are the state) ---
    def _ckpt_state(self) -> dict:
        return {
            "global_lora": self.global_lora,
            "round_idx": self.round_idx,
            "root_key": self.root_key,
        }

    def _apply_ckpt_state(self, state: dict) -> None:
        """Resume from a round checkpoint.  Not compatible across PR 29:
        ``lora.init_lora`` now factors ``attn/wo`` as (heads x head_dim, r) x
        (r, hidden), where it was (heads, r) x (r, head_dim x hidden), so a
        checkpoint whose targets include ``wo`` (the default) and that was saved
        before holds adapter shapes this simulator no longer draws."""
        self.global_lora = jax.tree_util.tree_map(jnp.asarray, state["global_lora"])
        self.round_idx = int(state["round_idx"])
        # checkpointed key is authoritative (same contract as MeshSimulator)
        self.root_key = jnp.asarray(state["root_key"])

    def run(self) -> list[dict]:
        history = []
        self.try_resume()
        while self.round_idx < self.cfg.comm_round:
            r = self.round_idx
            t0 = time.perf_counter()
            metrics = self.run_round()
            metrics.update(round=r, round_time_s=time.perf_counter() - t0)
            if self.cfg.frequency_of_the_test and (
                (r + 1) % self.cfg.frequency_of_the_test == 0 or r == self.cfg.comm_round - 1
            ):
                metrics.update(self.evaluate())
            self.logger.log(metrics)
            history.append(metrics)
            self.maybe_save_checkpoint(r)
        return history
