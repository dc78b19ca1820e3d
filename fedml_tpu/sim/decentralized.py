"""Decentralized FL — DSGD and PushSum over topology mixing matrices.

Reference: ``simulation/sp/decentralized/`` (``client_dsgd.py``,
``client_pushsum.py``) + ``core/distributed/topology/`` and the MPI
``decentralized_framework`` (gossip message passing between neighbor ranks).

TPU-native form (SURVEY.md §2.14 P10): all N clients' parameters live as one
stacked pytree sharded over the mesh; a gossip round is

    local SGD (vmap over clients)  ->  P' = W @ P   (mixing matmul)

The neighbor exchange that the reference implements with per-edge messages is
a single (N, N) x (N, d) matmul on the MXU — sparse topologies are just
sparse rows of W.  PushSum additionally threads the scalar weight column and
de-biases by it (directed graphs).
"""

from __future__ import annotations

import math
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..algorithms import hparams_from_config
from ..arguments import Config
from ..core import aot as aotlib, pytree as pt, rng
from ..core.flags import cfg_extra
from ..data.dataset import pad_eval_set, stack_clients
from ..fl.local_sgd import make_eval_fn, make_local_train_fn
from ..obs.metrics import MetricsLogger
from ..parallel import mesh as meshlib, topology as topo


class DecentralizedSimulator:
    """DSGD (symmetric row-stochastic W) / PushSum (column-stochastic directed
    W, so the de-bias ratio x/w recovers the uniform average)."""

    def __init__(self, cfg: Config, dataset, model, mesh=None, mode: str = None):
        self.cfg = cfg
        self.dataset = dataset
        self.model = model
        if mode is None:
            mode = cfg_extra(cfg, "decentralized_mode")
        self.mode = mode
        n = dataset.n_clients
        stacked = stack_clients(dataset, multiple_of=cfg.batch_size)
        spe = max(1, math.ceil(stacked.capacity / cfg.batch_size))
        self.hp = hparams_from_config(cfg, steps_per_epoch=spe)
        self._local_train = make_local_train_fn(model, self.hp)
        self.mesh = mesh if mesh is not None else meshlib.mesh_from_config(cfg)

        neighbor_num = int(cfg_extra(cfg, "topology_neighbor_num") or 2)
        if mode == "pushsum":
            # column-stochastic so the push weights evolve and x/w recovers
            # the uniform average (see topology.column_stochastic)
            W = topo.column_stochastic(
                topo.asymmetric_topology(n, neighbor_num, seed=cfg.random_seed)
            )
        elif mode == "ring":
            # uniform {prev, self, next} ring — mixed via ppermute halo
            # exchange (see _make_ring_mix), W kept only as the reference
            # matrix for parity checks
            if n < 3:
                # with n <= 2 prev == next, so the halo mix weights the single
                # neighbor twice ((x + 2*other)/3) while the dense
                # ring_topology reference collapses the duplicate edge —
                # the two would silently diverge
                raise ValueError(
                    f"mode='ring' needs n >= 3 clients (got {n}); use "
                    "mode='dsgd' for 1-2 clients"
                )
            W = topo.ring_topology(n)
        else:
            W = topo.symmetric_topology(n, neighbor_num, seed=cfg.random_seed)
        self.W = jnp.asarray(W)

        k0 = rng.root_key(cfg.random_seed)
        sample_x = jnp.asarray(stacked.x[0, : cfg.batch_size])
        one = model.init(
            {"params": jax.random.fold_in(k0, 1), "dropout": jax.random.fold_in(k0, 2)},
            sample_x, train=True,
        )
        # every client starts from the same init, stacked over clients
        self.client_vars = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), one
        )
        self.client_vars = meshlib.shard_leading_axis(self.client_vars, self.mesh)
        self.push_weights = jnp.ones((n,))  # PushSum de-bias column
        self._data = tuple(meshlib.shard_leading_axis((jnp.asarray(stacked.x), jnp.asarray(stacked.y)), self.mesh))
        self.counts = jnp.asarray(stacked.counts)
        self.root_key = k0
        self.round_idx = 0

        eval_bs = min(256, max(32, cfg.test_batch_size))
        tx, ty, n_valid = pad_eval_set(dataset.test_x, dataset.test_y, eval_bs)
        self._test = (jnp.asarray(tx), jnp.asarray(ty), jnp.int32(n_valid))
        self._eval_fn = jax.jit(make_eval_fn(model, self.hp, batch_size=eval_bs))
        self.logger = MetricsLogger(cfg.metrics_jsonl_path or None)
        # AOT program store (extra.aot_programs): ring gossip was 587 s of
        # recurring dryrun compile — warm restarts deserialize the exported
        # shard_map/ppermute program instead of re-tracing it.  Unset -> the
        # exact old jit path.
        self._aot = aotlib.store_from_config(cfg, trail=self.logger.log)
        round_fn = self._make_round_fn()
        if self._aot is not None:
            example = (self.client_vars, self.push_weights, self._data[0],
                       self._data[1], self.counts, jnp.int32(0), self.root_key)
            self._round_fn = self._aot.cached_jit(
                round_fn, example,
                key=aotlib.program_key(
                    "sim.gossip_round", mesh=self.mesh,
                    trees={"args": example}, hparams=self.hp,
                    config=aotlib.config_signature(cfg),
                    extra={"mode": self.mode, "neighbors": neighbor_num}),
            )
        else:
            self._round_fn = jax.jit(round_fn)

    def _gossip_axis(self) -> str:
        """The mesh axis the stacked-clients dim shards over (the same
        fallback convention as shard_leading_axis)."""
        if meshlib.AXIS_CLIENTS in self.mesh.shape:
            return meshlib.AXIS_CLIENTS
        return self.mesh.axis_names[0]

    def _make_ring_mix(self, n: int):
        """Ring gossip as ICI halo exchange: each device holds a contiguous
        block of clients; the two boundary rows travel via ``lax.ppermute``
        and everything else is a local shift.  Equivalent to
        ``ring_topology(n) @ P`` without ever materializing the (n, n)
        mixing matrix — per-round traffic is 2 rows/device instead of the
        full stacked model, which is what makes large-N sparse rings viable
        (reference P10 does this with per-edge MPI messages;
        ``decentralized_framework/algorithm_api.py``)."""
        from jax.sharding import PartitionSpec as P

        axis = self._gossip_axis()
        d = self.mesh.shape[axis]
        if n % d:
            raise ValueError(
                f"ring gossip needs the client count ({n}) divisible by the "
                f"{axis!r} mesh axis ({d}) — contiguous blocks per device"
            )
        fwd = [(i, (i + 1) % d) for i in range(d)]
        bwd = [(i, (i - 1) % d) for i in range(d)]

        def local_mix(block):
            # block: this device's (n/d, ...) rows.  Row j needs rows j-1 and
            # j+1; the block-edge neighbors live one device over.
            def leaf_mix(leaf):
                x = leaf.astype(jnp.float32)
                if d > 1:
                    prev_last = jax.lax.ppermute(x[-1:], axis, fwd)
                    next_first = jax.lax.ppermute(x[:1], axis, bwd)
                else:
                    prev_last, next_first = x[-1:], x[:1]
                left = jnp.concatenate([prev_last, x[:-1]], axis=0)
                right = jnp.concatenate([x[1:], next_first], axis=0)
                return ((x + left + right) / 3.0).astype(leaf.dtype)

            return jax.tree_util.tree_map(leaf_mix, block)

        spec = P(axis)
        return jax.shard_map(
            local_mix, mesh=self.mesh, in_specs=(spec,), out_specs=spec,
            check_vma=False,
        )

    def _make_round_fn(self):
        W = self.W
        mode = self.mode

        if mode == "ring":
            mix = self._make_ring_mix(int(self.counts.shape[0]))
        else:
            def mix(stacked_tree):
                return jax.tree_util.tree_map(
                    lambda leaf: jnp.tensordot(W, leaf.astype(jnp.float32), axes=([1], [0])).astype(leaf.dtype),
                    stacked_tree,
                )

        def round_fn(client_vars, push_w, data_x, data_y, counts, round_idx, key):
            rkey = rng.round_key(key, round_idx)
            n = counts.shape[0]
            keys = jax.vmap(lambda i: rng.client_key(rkey, i))(jnp.arange(n))
            trained, metrics = jax.vmap(
                lambda v, x, y, c, k: self._local_train(v, x, y, c, k, None)
            )(client_vars, data_x, data_y, counts, keys)
            if mode == "pushsum":
                # mix both the weighted params and the weights; de-bias
                weighted = jax.tree_util.tree_map(
                    lambda l: l * push_w.reshape((-1,) + (1,) * (l.ndim - 1)), trained
                )
                mixed = mix(weighted)
                new_w = W @ push_w
                debiased = jax.tree_util.tree_map(
                    lambda l: l / new_w.reshape((-1,) + (1,) * (l.ndim - 1)), mixed
                )
                return debiased, new_w, {k: jnp.mean(v) for k, v in metrics.items()}
            mixed = mix(trained)
            return mixed, push_w, {k: jnp.mean(v) for k, v in metrics.items()}

        return round_fn

    def run_round(self) -> dict:
        self.client_vars, self.push_weights, metrics = self._round_fn(
            self.client_vars, self.push_weights, self._data[0], self._data[1],
            self.counts, jnp.int32(self.round_idx), self.root_key,
        )
        self.round_idx += 1
        return {k: float(v) for k, v in metrics.items()}

    def consensus_model(self):
        """Average of all clients' models (the consensus point)."""
        return jax.tree_util.tree_map(lambda l: jnp.mean(l.astype(jnp.float32), axis=0).astype(l.dtype), self.client_vars)

    def consensus_distance(self) -> float:
        """Mean squared distance of clients to the consensus — the standard
        decentralized-convergence diagnostic."""
        mean = self.consensus_model()
        d = jax.tree_util.tree_map(
            lambda l, m: jnp.mean(jnp.sum((l.astype(jnp.float32) - m[None].astype(jnp.float32)) ** 2,
                                          axis=tuple(range(1, l.ndim)))),
            self.client_vars, mean,
        )
        return float(jax.tree_util.tree_reduce(jnp.add, d, jnp.float32(0)))

    def evaluate(self) -> dict:
        return {k: float(v) for k, v in self._eval_fn(self.consensus_model(), *self._test).items()}

    def run(self) -> list[dict]:
        history = []
        for r in range(self.cfg.comm_round):
            t0 = time.perf_counter()
            metrics = self.run_round()
            metrics.update(round=r, round_time_s=time.perf_counter() - t0)
            if self.cfg.frequency_of_the_test and (
                (r + 1) % self.cfg.frequency_of_the_test == 0 or r == self.cfg.comm_round - 1
            ):
                metrics.update(self.evaluate())
                metrics["consensus_dist"] = self.consensus_distance()
            self.logger.log(metrics)
            history.append(metrics)
        return history
