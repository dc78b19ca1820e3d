"""MeshSimulator — FL simulation as one sharded, jitted program per round.

This subsumes the reference's three simulation backends (SURVEY.md §2.3):
- SP sequential loop        (``simulation/sp/fedavg/fedavg_api.py:66-177``)
- MPI worker processes      (``simulation/mpi/fedavg/FedAvgAPI.py``)
- NCCL LocalAggregators     (``simulation/nccl/base_framework/common.py:129``)

On TPU there is no actor system: the round IS a compiled function.

    round(global_vars, server_state, client_states, round_idx, key):
      sampled  = permutation-sample m of N client ids        (device-side)
      shards   = gather client data + state by id            (jnp.take)
      outputs  = vmap(algorithm.client_update) over clients  (sharded on mesh)
      agg      = hooks(before_agg) -> algorithm.aggregate    (all-reduce)
      global'  = algorithm.server_update(agg)
      states'  = scatter refreshed client states back

The ``clients`` mesh axis shards the vmapped dimension and the stacked client
data/state, so local SGD runs on every chip in parallel and the weighted mean
lowers to one ICI all-reduce — the reference's whole process/message machinery
(bullets P1-P3 of SURVEY.md §2.14) collapses into sharding annotations.

A vmapped lane pays for every step of the loop it shares, masked or not
(``fl/local_sgd.py``, "Ragged client shards").  So where ``step_mode="match"``
gives the population's clients different step budgets, the round sorts its
lanes by budget and runs them in buckets, one after another inside the same
program, each with a step loop that ends at the bucket's own longest client
(``_bucket_count``, ``_order_lanes``, ``_run_lane_buckets``).  Equal budgets
build the one vmap.

``backend="sp"`` runs the same pure functions in a host loop over clients
(one jitted client_update at a time) — the numerics-regression twin of the
reference's single-process simulator; tests assert MESH == SP.
"""

from __future__ import annotations

import math
import time
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import constants as C
from ..algorithms import create as create_algorithm, hparams_from_config
from ..analysis import tracesan
from ..arguments import Config
from ..core import aot as aotlib, pytree as pt, rng
from ..core.flags import cfg_extra
from ..data.dataset import FederatedDataset, StackedClientData, pad_eval_set, stack_clients
from ..fl.local_sgd import eval_batch_size, make_eval_fn, own_step_budget
from ..parallel import mesh as meshlib
from ..obs import otlp as obsotlp, registry as obsreg, scopes
from ..obs.metrics import MetricsLogger
from ..obs.trace import XLA_COUNTERS, traced
from ..ops import flops as flopslib

# measurement substrate for perf work (ISSUE 1): compile vs execute split,
# program-cache hit rate, round/eval wall time — all scrapable via /metrics
ROUND_TIME = obsreg.REGISTRY.histogram(
    "fedml_sim_round_seconds",
    "Per-round wall time (chunk-averaged inside scanned chunks).",
)
CHUNK_COMPILE_TIME = obsreg.REGISTRY.histogram(
    "fedml_sim_chunk_compile_seconds",
    "jit(scan(round)) chunk program compile time.",
)
CHUNK_EXECUTE_TIME = obsreg.REGISTRY.histogram(
    "fedml_sim_chunk_execute_seconds",
    "Scanned-chunk execute wall time (dispatch to host sync, post-compile).",
)
EVAL_TIME = obsreg.REGISTRY.histogram(
    "fedml_sim_eval_seconds",
    "Server-side evaluation wall time.",
)
CHUNK_CACHE = obsreg.REGISTRY.counter(
    "fedml_sim_chunk_cache_total",
    "Scanned-chunk program cache lookups; jit cache hits are the "
    "hit/miss delta over time.",
    labels=("result",),
)
ACHIEVED_FLOPS = obsreg.REGISTRY.gauge(
    "fedml_sim_achieved_flops_per_sec",
    "XLA cost-model FLOPs of the last executed chunk divided by its wall "
    "time (extra.cost_model_gauges).",
)
SIM_MFU = obsreg.REGISTRY.gauge(
    "fedml_sim_mfu",
    "Model FLOP utilization of the last executed chunk: achieved FLOP/s "
    "over the device peak (ops/flops.py; never set off-TPU — a CPU run "
    "reports achieved FLOP/s only).  extra.cost_model_gauges.",
)

SAMPLES = obsreg.REGISTRY.counter(
    "fedml_sim_samples_total",
    "Samples of local SGD in the scanned chunks: kind=real is the sampled "
    "clients' own counts x epochs (summed on the device, where they are "
    "sampled), kind=lane what the lanes computed, masked steps and cyclic "
    "padding included: lanes x steps x batch, with the steps each lane "
    "bucket's loop really ran summed on the device (a program without "
    "buckets runs epochs x steps per epoch in every lane).  real/lane is the "
    "useful share of lane work.",
    labels=("kind",),
)
#: the round program's extra stacked outputs that carry kind=real, and the
#: bucketed program's lane-steps for kind=lane, to the host; taken out before
#: the per-round metric dicts are built
REAL_COUNT_KEY = "_real_count"
LANE_STEPS_KEY = "_lane_steps"

from ..core.checkpoint import RoundCheckpointMixin


class MeshSimulator(RoundCheckpointMixin):
    #: optional GangScheduler hook (cross_silo/runtime.py): when the
    #: multi-tenant control plane attaches one, each population cohort
    #: round requests a slot/lease before touching the mesh and releases
    #: it after the round commits — the same round-boundary arbitration
    #: the cross-silo servers use.  None (the default) = ungated,
    #: bit-identical to before the hook existed.
    round_gate = None

    @traced("sim.init")
    def __init__(
        self,
        cfg: Config,
        dataset: FederatedDataset,
        model,
        algorithm=None,
        mesh=None,
        trust=None,
        logger: Optional[MetricsLogger] = None,
    ):
        self.cfg = cfg
        self.dataset = dataset
        self.model = model
        self.backend = cfg.backend_sim if cfg.backend_sim else C.SIMULATION_BACKEND_MESH
        if trust is None:
            from ..trust.pipeline import build_trust_pipeline

            trust = build_trust_pipeline(cfg)
        self.trust = trust
        if trust is not None and trust.attacker is not None and trust.attacker.is_data_attack():
            dataset = trust.attacker.poison_data(dataset)
            self.dataset = dataset
        self.logger = logger or MetricsLogger(cfg.metrics_jsonl_path or None)
        # ahead-of-time program store (extra.aot_programs, ISSUE 7): the
        # scanned-chunk / population-round / eval programs are
        # jax.export-serialized under a tracing fingerprint so a restarted
        # server deserializes instead of re-tracing.  Flag unset -> None and
        # every jit below runs the exact pre-store path (bit-identical).
        self._aot = aotlib.store_from_config(cfg, trail=self.logger.log)
        # cost-model gauges (ISSUE 16 satellite): per-program flops/bytes at
        # compile, achieved-FLOP/s + MFU per executed chunk.  Flag unset ->
        # zero extra work on any hot path.
        self._cost_gauges = bool(cfg_extra(cfg, "cost_model_gauges"))
        self._chunk_flops: dict = {}

        # ---- data: pad + stack, shard over the clients axis ----
        with traced("sim.init.stack_clients"):
            stacked = stack_clients(dataset, multiple_of=cfg.batch_size)
        self.capacity = stacked.capacity
        steps_per_epoch = max(1, math.ceil(self.capacity / cfg.batch_size))
        self.hp = hparams_from_config(cfg, steps_per_epoch=steps_per_epoch)
        self.algorithm = (algorithm or create_algorithm(cfg, self.hp)).build(model)

        self.mesh = mesh if mesh is not None else meshlib.mesh_from_config(cfg)
        # Client-axis padding (SURVEY §7 hard-part 2): stacks whose leading
        # (client) dim is not a multiple of the mesh axis would REPLICATE
        # (shard_leading_axis's correctness fallback) and serialize all client
        # compute.  Pad the stack with zero-count dummy rows instead; dummies
        # are never sampled (sampling stays over n_clients) and never
        # scattered to, so numerics are untouched.
        self._client_axis, self._lane_multiple = self._client_axis_info()
        #: lanes a mesh round computes: the sampled clients padded to the mesh
        self._lanes = meshlib.round_up(
            min(cfg.client_num_per_round, dataset.n_clients), self._lane_multiple)
        self._n_real = dataset.n_clients
        self._lane_buckets = self._bucket_count(stacked.counts)
        self._n_pad = meshlib.round_up(self._n_real, self._lane_multiple)
        if self._n_pad > self._n_real:
            stacked = StackedClientData(
                x=meshlib.pad_leading_axis_np(stacked.x, self._n_pad),
                y=meshlib.pad_leading_axis_np(stacked.y, self._n_pad),
                counts=meshlib.pad_leading_axis_np(stacked.counts, self._n_pad),
            )
        with traced("sim.init.place_data"):
            self._data = self._place_data(stacked)
        # replicate ONCE at init: a bare jnp.asarray stays single-device and
        # every mesh dispatch would re-reshard it device-to-device per call
        # (witnessed by TRACESAN's round guard)
        self.counts = (jnp.asarray(stacked.counts)
                       if self.backend == C.SIMULATION_BACKEND_SP
                       else meshlib.replicate(stacked.counts, self.mesh))

        # ---- model/state init ----
        k0 = rng.root_key(cfg.random_seed)
        sample_x = jnp.asarray(stacked.x[0, : cfg.batch_size])
        with traced("sim.init.model_init"):
            self.global_vars = self.model.init(
                {"params": jax.random.fold_in(k0, 1), "dropout": jax.random.fold_in(k0, 2)},
                sample_x, train=True,
            )
            self.global_vars = meshlib.replicate(jax.device_get(self.global_vars), self.mesh)
        self.server_state = self.algorithm.init_server_state(self.global_vars)
        cs_template = self.algorithm.init_client_state(self.global_vars)
        if cs_template is not None:
            n = self._n_pad  # dummy rows are never gathered or scattered
            stacked_cs = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), cs_template
            )
            self.client_states = meshlib.shard_leading_axis(stacked_cs, self.mesh)
        else:
            self.client_states = None

        # ---- test data (tiled to eval batch multiple) ----
        # the batch follows the test set and what a sample is to the model (its
        # input and its logits); the clients' batch is only its floor
        probe = jax.ShapeDtypeStruct((1,) + dataset.test_x.shape[1:], dataset.test_x.dtype)
        logits = jax.eval_shape(lambda v, x: model.apply(v, x, train=False), self.global_vars, probe)
        eval_bs = eval_batch_size(
            dataset.test_x.shape[0], math.prod(probe.shape) + math.prod(logits.shape),
            floor=int(np.clip(cfg.test_batch_size, 32, 256)), lanes=self._eval_lanes())
        tx, ty, n_test = pad_eval_set(dataset.test_x, dataset.test_y, eval_bs)
        # placed like global_vars (replicated over the mesh): left on the
        # default device, every evaluate() would re-stage the test set from
        # chip 0 to the rest of the mesh
        test = (tx, ty, np.int32(n_test))
        with traced("sim.init.eval_fn"):
            self._test = (tuple(jnp.asarray(t) for t in test)
                          if self.backend == C.SIMULATION_BACKEND_SP
                          else tuple(meshlib.replicate(test, self.mesh)))
            self._eval_bs = eval_bs  # the padding multiple of self._test
            eval_fn = make_eval_fn(model, self.hp, batch_size=eval_bs)
            if self._aot is not None:
                self._eval_fn = self._aot.cached_jit(
                    eval_fn, (self.global_vars, *self._test),
                    key=self._aot_key("sim.eval", trees={
                        "global_vars": self.global_vars, "test": self._test},
                        extra={"eval_batch": eval_bs}),
                )
            else:
                self._eval_fn = jax.jit(eval_fn)
                scopes.note_program("sim.eval", self._eval_fn, (self.global_vars, *self._test))

        # OTLP egress (gated on extra.otlp_endpoint; None -> spans keep
        # their no-sink default and no exporter thread exists): the
        # simulator's chunk/eval spans flow to the same collector the
        # cross-silo server exports to
        self._otlp = obsotlp.exporter_from_config(cfg)
        self._otlp_sink = self._otlp.enqueue_span if self._otlp is not None else None

        # replicated at init for the same reason as counts: the key is a
        # per-dispatch argument of every mesh round program
        self.root_key = self._stage_scalar(k0)
        self.round_idx = 0
        # history for cross-round defenses: flat global delta of the previous
        # round, threaded through the jitted round as a real argument (a
        # captured attribute would be baked in at trace time)
        if self.trust is not None and self.trust.needs_history:
            flat, _ = pt.tree_flatten_to_vector(self.global_vars)
            self.defense_history = jnp.zeros_like(flat)
        else:
            self.defense_history = None
        self._round_fn = jax.jit(self._make_round_fn()) if self.backend != C.SIMULATION_BACKEND_SP else None
        self._client_fn_sp = jax.jit(self._sp_client_update) if self.backend == C.SIMULATION_BACKEND_SP else None
        # scanned multi-round programs, keyed by chunk length (one compile per
        # distinct length); see run_rounds
        self._multi_round_fns: dict[int, Callable] = {}

        # -- population mode (extra.population_store): stream per-round
        # cohorts from the sharded on-disk store instead of sampling the
        # device-resident stack.  Everything above stays as-is — the base
        # dataset is small by construction (the store replicates it across
        # the population) and the default path is untouched when unset.
        self._population = None
        pop_root = cfg_extra(cfg, "population_store")
        if pop_root:
            if self.backend == C.SIMULATION_BACKEND_SP:
                raise ValueError(
                    "population_store streams cohorts into the vmapped MESH "
                    "round; it has no meaning on the SP host loop")
            self._init_population(str(pop_root), stacked)

    # ------------------------------------------------------------------
    def _client_axis_info(self) -> tuple[str, int]:
        """(axis name, axis size) the stacked-client dim shards over; size 1
        on the SP backend (no padding needed for a host loop)."""
        if self.backend == C.SIMULATION_BACKEND_SP:
            return meshlib.AXIS_CLIENTS, 1
        axis = (meshlib.AXIS_CLIENTS if meshlib.AXIS_CLIENTS in self.mesh.shape
                else self.mesh.axis_names[0])
        return axis, int(self.mesh.shape[axis])

    def _eval_lanes(self) -> int:
        """Model copies ONE step of an evaluation program scores (the engine
        evaluates the global model; MyAvg also vmaps every personal one)."""
        return 1

    def _bucket_count(self, counts) -> int:
        """How many lane buckets the round program runs one after another;
        1 is one ``vmap`` over all lanes, the program as it always was.

        A lane pays for every step of the loop it is vmapped into, masked or
        not.  Where ``step_mode="match"`` gives the population's clients
        different step budgets, the round sorts its lanes by budget and runs
        them in buckets whose loop ends at the bucket's own longest client
        (``_make_round_fn``).  Nothing to gain, so nothing built, where the
        budgets are equal, steps are not masked, or the algorithm has no step
        loop (``FedAlgorithm.has_step_loop``).

        A bucket is twice as many lanes wide as an epoch has steps (the next
        width that divides the lanes and fills every chip of the clients axis
        alike).  Along the sorted lanes the budget changes at most
        ``steps_per_epoch - 1`` times, so that many buckets at most hold lanes
        shorter than their longest (in ``fedavg_r20.cross_device`` 48% of the
        lanes' steps were masked, 8% still are).  Every bucket is a loop
        to start, so narrower ones are not free: a model whose step is bound
        by the launch of its ops pays about 35 us a bucket, and buckets as
        wide as ONE epoch made ``fedavg_r20.cross_device`` issue more device
        ops in its 20 s window than a profile keeps (PERF.md section 6,
        PR 27, has both readings)."""
        hp = self.hp
        if (self.backend == C.SIMULATION_BACKEND_SP or hp.step_mode != "match"
                or not self.algorithm.has_step_loop):
            return 1
        budgets = own_step_budget(hp, np.asarray(counts[: self.dataset.n_clients]))
        if budgets.min() == budgets.max():
            return 1
        per_chip = self._lanes // self._lane_multiple
        wide = next(w for w in range(1, per_chip + 1)
                    if per_chip % w == 0 and w * self._lane_multiple >= min(2 * hp.steps_per_epoch, self._lanes))
        return per_chip // wide

    def _order_lanes(self, lanes, counts):
        """The bucketed round's own step before the shared gather: the lane
        ids by step budget, longest first (stable, so equal budgets keep the
        sampled order), so that a bucket's lanes have like budgets, and the
        permutation that puts what the lanes return back in the order of
        ``lanes``."""
        budgets = own_step_budget(self.hp, jnp.take(counts, lanes))
        order = jnp.argsort(-budgets, stable=True)
        return jnp.take(lanes, order), jnp.argsort(order)

    def _run_lane_buckets(self, run_lanes, lanes_in):
        """Run the lanes ``(cs, xs, ys, cnts, keys)`` bucket after bucket: ONE
        bucket body in the program, scanned over the bucket axis.  A bucket is
        the plain round's ``run_lanes`` over its lanes, with a step loop that
        ends at the longest budget among them (one trip count for all of a
        bucket's lanes).  Returns what the lanes returned, in their order, and
        the lane-steps the buckets ran."""
        k, per_bucket = self._lane_buckets, self._lanes // self._lane_buckets

        def bucket(lane_steps, of_bucket):
            cs, xs, ys, cnts, keys = of_bucket
            bound = jnp.max(own_step_budget(self.hp, cnts))
            out = run_lanes(self._constrain_lanes(cs), self._constrain_lanes(xs),
                            self._constrain_lanes(ys), cnts, keys, step_bound=bound)
            return lane_steps + per_bucket * bound, out

        lane_steps, out = jax.lax.scan(bucket, jnp.int32(0), jax.tree_util.tree_map(
            lambda a: a.reshape((k, per_bucket) + a.shape[1:]), lanes_in))
        return jax.tree_util.tree_map(
            lambda a: a.reshape((k * per_bucket,) + a.shape[2:]), out), lane_steps

    def _pad_lanes(self, sampled, m: int, m_pad: int):
        """Extend the sampled id vector with client-0 lanes up to the mesh
        multiple.  Pad lanes redo client 0's local SGD (same cost as an idle
        replicated lane, but the real lanes stay sharded); their outputs are
        sliced away before the server path, so aggregation, trust hooks and
        metrics see exactly the real ``m`` clients."""
        if m_pad == m:
            return sampled
        return jnp.concatenate([sampled, jnp.zeros(m_pad - m, jnp.int32)])

    def _constrain_lanes(self, tree):
        """Pin the vmapped-client dim to the clients axis — GSPMD would
        otherwise be free to replicate the gathered per-lane operands."""
        if self._lane_multiple <= 1 or tree is None:
            return tree
        mesh, axis = self.mesh, self._client_axis
        return jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(axis, *([None] * (x.ndim - 1))))
            ),
            tree,
        )

    @staticmethod
    def _slice_lanes(tree, m: int):
        return jax.tree_util.tree_map(lambda a: a[:m], tree)

    def _gather_round_inputs(self, sampled, m, m_pad, counts, data_x, data_y,
                             client_states, key, round_idx):
        """Shared per-round gather: pad the sampled ids to the lane multiple,
        pull each lane's data/state/count/key, and pin the lane dim to the
        clients axis.  Both the FedAvg-family round and the MyAvg round use
        this verbatim — lane handling must never diverge between them."""
        lanes = self._pad_lanes(sampled, m, m_pad)
        xs = self._constrain_lanes(jnp.take(data_x, lanes, axis=0))
        ys = self._constrain_lanes(jnp.take(data_y, lanes, axis=0))
        cnts = jnp.take(counts, lanes)
        cs = self._constrain_lanes(
            pt.tree_take(client_states, lanes) if client_states is not None else None
        )
        rkey = rng.round_key(key, round_idx)
        keys = jax.vmap(lambda i: rng.client_key(rkey, i))(lanes)
        return xs, ys, cnts, cs, rkey, keys

    # ------------------------------------------------------------------
    def _place_data(self, stacked: StackedClientData):
        x, y = np.asarray(stacked.x), np.asarray(stacked.y)
        if self.hp.compute_dtype == "bfloat16" and np.issubdtype(x.dtype, np.floating):
            # store device-resident shards in the compute dtype: halves HBM
            # footprint AND the per-round sampled-client gather traffic.
            # Cast on the host so each shard goes straight to its own chip:
            # staging the whole f32 stack through the default device first
            # makes chip 0 hold every client's data at once
            import ml_dtypes

            x = x.astype(ml_dtypes.bfloat16)
        if self.backend == C.SIMULATION_BACKEND_SP:
            return (jnp.asarray(x), jnp.asarray(y))
        return tuple(meshlib.shard_leading_axis((x, y), self.mesh))

    # ------------------------------------------------------------------
    def _make_round_fn(self):
        algo = self.algorithm
        cfg = self.cfg
        n_total = self.dataset.n_clients
        m = min(cfg.client_num_per_round, n_total)
        m_pad = self._lanes
        buckets = self._lane_buckets

        def round_fn(global_vars, server_state, client_states, counts, data_x, data_y, round_idx, key, prev_delta):
            # the scopes name each op's phase in a device profile (XProf)
            with jax.named_scope("fl.gather"):
                sampled = rng.sample_clients(key, round_idx, n_total, m)
                lanes = sampled
                if buckets > 1:  # lanes of like budgets together
                    lanes, restore = self._order_lanes(self._pad_lanes(sampled, m, m_pad), counts)
                xs, ys, cnts, cs, rkey, keys = self._gather_round_inputs(
                    lanes, lanes.shape[0], m_pad, counts, data_x, data_y, client_states, key, round_idx
                )

            def one_client(cstate, x, y, cnt, k, **bound):
                out = algo.client_update(global_vars, cstate, server_state, x, y, cnt, k, **bound)
                return out.contribution, out.client_state, out.metrics

            def run_lanes(cs, xs, ys, cnts, keys, **bound):
                if cs is not None:
                    return jax.vmap(partial(one_client, **bound), in_axes=(0, 0, 0, 0, 0))(cs, xs, ys, cnts, keys)
                return jax.vmap(
                    lambda x, y, cnt, k: one_client(None, x, y, cnt, k, **bound)
                )(xs, ys, cnts, keys)

            with jax.named_scope("fl.local_sgd"):
                if buckets > 1:
                    (contribs, new_cs, metrics), lane_steps = self._run_lane_buckets(
                        run_lanes, (cs, xs, ys, cnts, keys))
                    # back in the order of ``sampled``: the fold, the trust
                    # hooks and the scatter see what they always saw
                    contribs, new_cs, metrics, cnts = jax.tree_util.tree_map(
                        lambda a: jnp.take(a, restore, axis=0), (contribs, new_cs, metrics, cnts))
                else:
                    contribs, new_cs, metrics = run_lanes(cs, xs, ys, cnts, keys)

            with jax.named_scope("fl.fold"):
                # drop the pad lanes: everything downstream (trust hooks,
                # aggregation, scatter, metrics) sees exactly the real m clients
                contribs = self._slice_lanes(contribs, m)
                new_cs = self._slice_lanes(new_cs, m) if new_cs is not None else None
                metrics = self._slice_lanes(metrics, m)
                weights = cnts[:m].astype(jnp.float32)
                new_global, new_server, new_delta = self._server_path(
                    contribs, weights, sampled, global_vars, server_state, rkey, round_idx, prev_delta
                )

                if client_states is not None:
                    new_states = jax.tree_util.tree_map(
                        lambda full, upd: full.at[sampled].set(upd), client_states, new_cs
                    )
                else:
                    new_states = None
                round_metrics = {k: jnp.mean(v) for k, v in metrics.items()}
                # what this round really trained on, said by the program
                # that sampled it (fedml_sim_samples_total{kind="real"})
                round_metrics[REAL_COUNT_KEY] = jnp.sum(cnts[:m])
                if buckets > 1:
                    # and what its lanes computed (kind="lane")
                    round_metrics[LANE_STEPS_KEY] = lane_steps
            return new_global, new_server, new_states, new_delta, round_metrics

        return round_fn

    def _server_path(self, contribs, weights, sampled, global_vars, server_state, rkey, round_idx, prev_delta):
        """Trust hooks + aggregation + server update — shared by the MESH
        round program and the SP host loop, so security semantics are
        backend-independent."""
        algo = self.algorithm
        if self.trust is not None:
            contribs, weights = self.trust.on_client_outputs(
                contribs, weights, sampled, global_vars, rkey
            )
            contribs, weights, agg_override = self.trust.on_aggregation(
                contribs, weights, global_vars, rkey, prev_delta=prev_delta
            )
        else:
            agg_override = None
        agg = agg_override if agg_override is not None else algo.aggregate(contribs, weights)
        new_global, new_server = algo.server_update(global_vars, server_state, agg, round_idx)
        if self.trust is not None:
            new_global = self.trust.on_after_aggregation(new_global, global_vars, rkey)
        new_delta = None
        if prev_delta is not None:
            new_flat, _ = pt.tree_flatten_to_vector(new_global)
            old_flat, _ = pt.tree_flatten_to_vector(global_vars)
            new_delta = new_flat - old_flat
        return new_global, new_server, new_delta

    def _sp_client_update(self, global_vars, cstate, server_state, x, y, cnt, key):
        out = self.algorithm.client_update(global_vars, cstate, server_state, x, y, cnt, key)
        return out.contribution, out.client_state, out.metrics

    # -- population mode (extra.population_store) ----------------------------
    def _init_population(self, root: str, stacked) -> None:
        """Assemble the sharded store + hierarchical sampler + prefetch
        pipeline (fedml_tpu/population/) and the jitted cohort round.  The
        store — not a device stack — is the authority for per-client state
        in this mode, so the device-stacked ``client_states`` is dropped."""
        from types import SimpleNamespace

        from ..population import build_population_components

        cs_template = self.algorithm.init_client_state(self.global_vars)
        state_template = (
            jax.device_get(cs_template) if cs_template is not None else None
        )
        n_real = self._n_real
        store, sampler, pipeline = build_population_components(
            self.cfg, root,
            stacked.x[:n_real], stacked.y[:n_real], stacked.counts[:n_real],
            self.capacity, state_template=state_template,
        )
        m = sampler.cohort_size
        m_pad = meshlib.round_up(m, self._lane_multiple)
        # with the AOT store the cohort round binds lazily at round 0 (the
        # export fingerprint wants the real stacked example args); without it
        # the program is jitted here exactly as before
        self._population = SimpleNamespace(
            store=store, sampler=sampler, pipeline=pipeline,
            m=m, m_pad=m_pad,
            round_fn=(jax.jit(self._make_population_round_fn(m))
                      if self._aot is None else None),
        )
        self.client_states = None  # per-client state lives in the store

    def _make_population_round_fn(self, m: int):
        """The cohort round: same client math, trust hooks, and server path
        as :meth:`_make_round_fn`, but the cohort's data/state arrive as
        stacked arguments (host-gathered from the store) instead of being
        jnp.take'd out of a device-resident population stack, and the
        sampled ids ride in as ``lane_ids`` so per-client RNG keys fold the
        same streams the in-memory path folds."""
        algo = self.algorithm

        def round_fn(global_vars, server_state, cs, cnts, xs, ys, lane_ids,
                     round_idx, key, prev_delta):
            xs = self._constrain_lanes(xs)
            ys = self._constrain_lanes(ys)
            cs = self._constrain_lanes(cs)
            rkey = rng.round_key(key, round_idx)
            keys = jax.vmap(lambda i: rng.client_key(rkey, i))(lane_ids)

            def one_client(cstate, x, y, cnt, k):
                out = algo.client_update(global_vars, cstate, server_state, x, y, cnt, k)
                return out.contribution, out.client_state, out.metrics

            if cs is not None:
                contribs, new_cs, metrics = jax.vmap(one_client, in_axes=(0, 0, 0, 0, 0))(cs, xs, ys, cnts, keys)
            else:
                contribs, new_cs, metrics = jax.vmap(
                    lambda x, y, cnt, k: one_client(None, x, y, cnt, k)
                )(xs, ys, cnts, keys)
            contribs = self._slice_lanes(contribs, m)
            new_cs = self._slice_lanes(new_cs, m) if new_cs is not None else None
            metrics = self._slice_lanes(metrics, m)
            weights = cnts[:m].astype(jnp.float32)
            new_global, new_server, new_delta = self._server_path(
                contribs, weights, lane_ids[:m], global_vars, server_state,
                rkey, round_idx, prev_delta,
            )
            round_metrics = {k: jnp.mean(v) for k, v in metrics.items()}
            return new_global, new_server, new_cs, new_delta, round_metrics

        return round_fn

    @staticmethod
    def _pad_cohort_rows(tree, m_pad: int):
        """Row-repeat lane padding on host arrays: pad lanes replay row 0
        (the same client the padded ID vector repeats); they are sliced away
        on device before aggregation and never scattered back."""
        def pad(a):
            a = np.asarray(a)
            if a.shape[0] >= m_pad:
                return a
            reps = np.concatenate([
                np.arange(a.shape[0]), np.zeros(m_pad - a.shape[0], np.int64)])
            return a[reps]

        return jax.tree_util.tree_map(pad, tree)

    def _run_population_rounds(self, n: int) -> list[dict]:
        """Streamed cohort execution: gather cohort r+1's data on the
        prefetch thread while cohort r runs through the vmapped round, then
        scatter refreshed per-client state back to its shards.  State is
        gathered on the critical path AFTER the previous round's scatter —
        a client sampled in consecutive cohorts must see its fresh state."""
        pop = self._population
        out = []
        for _ in range(n):
            if self.round_gate is not None:
                # cohort rounds arbitrate through the same device-slot
                # scheduler as the cross-silo servers: block this (caller)
                # thread until the slot/lease grant lands, run the round,
                # release at the round boundary
                import threading

                granted = threading.Event()
                self.round_gate.request(self, granted.set)
                granted.wait()
            try:
                out.append(self._run_one_population_round())
            finally:
                if self.round_gate is not None:
                    self.round_gate.release(self)
        # host boundary: the on-disk shards are this mode's checkpointable
        # client state — keep them consistent before eval/checkpoint runs
        pop.store.flush()
        return out

    def _run_one_population_round(self) -> dict:
        """One streamed cohort round (the body :meth:`_run_population_rounds`
        gates); returns the round's host metrics."""
        from ..population.cohorts import CohortPipeline

        pop = self._population
        r = self.round_idx
        t0 = time.perf_counter()
        pop.pipeline.prefetch_round(r)
        ids, batch = pop.pipeline.obtain(r)
        if r + 1 < self.cfg.comm_round:
            pop.pipeline.prefetch_round(r + 1)
        lanes = CohortPipeline.pad_ids(ids, pop.m_pad)
        xs = self._pad_cohort_rows(batch.x, pop.m_pad)
        if self.hp.compute_dtype == "bfloat16" and np.issubdtype(xs.dtype, np.floating):
            import ml_dtypes

            xs = xs.astype(ml_dtypes.bfloat16)
        ys = self._pad_cohort_rows(batch.y, pop.m_pad)
        cs = pop.store.gather_state(ids)
        if cs is not None:
            cs = meshlib.shard_leading_axis(
                self._pad_cohort_rows(cs, pop.m_pad), self.mesh)
        xs, ys = meshlib.shard_leading_axis((xs, ys), self.mesh)
        cnts = jnp.asarray(self._pad_cohort_rows(batch.counts, pop.m_pad))
        args = (
            self.global_vars, self.server_state, cs, cnts, xs, ys,
            jnp.asarray(lanes, jnp.int32), jnp.int32(r), self.root_key,
            self.defense_history,
        )
        if pop.round_fn is None:
            # first cohort with the AOT store: load (or export) the
            # round program — a restarted server skips the re-trace
            raw = self._make_population_round_fn(pop.m)
            pop.round_fn = self._aot.cached_jit(
                raw, args,
                key=self._aot_key("sim.population_round",
                                  trees={"args": args},
                                  extra={"cohort": pop.m}),
            )
        with traced("sim.population_round", round_idx=r, cohort=pop.m,
                    sink=self._otlp_sink):
            with tracesan.round_guard(r):
                gv, ss, new_cs, nd, metrics = pop.round_fn(*args)
            with tracesan.allow("round_metrics"):
                host = {k: float(v) for k, v in metrics.items()}  # graftlint: disable=GL010(annotated measurement site: round-boundary metric export — one scalar-dict sync per cohort round, behind the TRACESAN round_metrics allowlist)
        if new_cs is not None:
            pop.store.scatter_state(ids, new_cs)
        self.global_vars, self.server_state = gv, ss
        if nd is not None:
            self.defense_history = nd
        self.round_idx += 1
        ROUND_TIME.observe(time.perf_counter() - t0)
        return host

    # ------------------------------------------------------------------
    def _aot_key(self, site: str, trees: Optional[dict] = None,
                 extra: Optional[dict] = None) -> str:
        """Program-store fingerprint for one of this simulator's traced
        programs: mesh + argument tree signatures + hparams + the full
        (volatile-stripped) config, so any knob that changes tracing — chunk
        size, codec/trust flags, donation gating — changes the
        key (see core/aot.py)."""
        return aotlib.program_key(
            site,
            mesh=None if self.backend == C.SIMULATION_BACKEND_SP else self.mesh,
            trees=trees,
            hparams=self.hp,
            config=aotlib.config_signature(self.cfg),
            extra=dict(extra or {}, backend_sim=self.backend),
        )

    def warm_start(self) -> dict:
        """The AOT store's ``warm()`` path: pre-load (or pre-build) every
        scanned-chunk program :meth:`run` will need before round 0, so a
        restarted server's first round pays dispatch, not tracing.  No-op
        without ``extra.aot_programs`` / off the mesh chunk path."""
        if (self._aot is None or self._population is not None
                or self.backend == C.SIMULATION_BACKEND_SP):
            return {"warmed": 0}
        lengths, r = set(), self.round_idx
        while r < self.cfg.comm_round:
            end = self._next_boundary(r)
            lengths.add(end - r)
            r = end
        args = (
            self.global_vars, self.server_state, self.client_states,
            self.counts, self._data[0], self._data[1],
            jnp.int32(self.round_idx), self.root_key, self.defense_history,
        )
        for n in sorted(lengths):
            self._get_multi_round_fn(n, example_args=args)
        return {"warmed": len(lengths)}

    def _get_multi_round_fn(self, n: int, example_args: Optional[tuple] = None):
        """jit(scan(round)) over ``n`` rounds — ONE dispatch and ONE host
        sync per chunk.  Every host<->device round trip is latency (per-round
        metric pulls measured 3-8x the round's compute, PERF.md); the round
        loop belongs on the device, which is exactly SURVEY.md §7's
        ``jit(scan(round))`` form.

        With ``example_args`` the chunk is AOT-compiled (lower + compile)
        so compile time is measured separately from execute time.  The
        carried state is donated on every backend.

        With ``extra.aot_programs`` the chunk program comes out of the AOT
        program store (core/aot.py): a warm process deserializes the exported
        StableHLO instead of re-tracing, and the wrapper's compile goes back
        through the persistent compilation cache.  The stored artifact is
        donation-free; donation is applied on the wrapper."""
        fn = self._multi_round_fns.get(n)
        if fn is not None:
            CHUNK_CACHE.inc(result="hit")
            return fn
        CHUNK_CACHE.inc(result="miss")
        round_fn = self._make_round_fn()

        def multi(global_vars, server_state, client_states, counts, data_x, data_y,
                  start_round, key, prev_delta):
            def body(carry, r):
                gv, ss, cs, pd = carry
                ngv, nss, ncs, nd, metrics = round_fn(gv, ss, cs, counts, data_x, data_y, r, key, pd)
                return (ngv, nss, ncs, nd), metrics
            (gv, ss, cs, pd), stacked_metrics = jax.lax.scan(
                body, (global_vars, server_state, client_states, prev_delta),
                start_round + jnp.arange(n, dtype=jnp.int32),
            )
            return gv, ss, cs, pd, stacked_metrics

        # donate the big carried state: the round rewrites params/opt/client
        # stacks in place instead of holding two copies in HBM
        donate = (0, 1, 2, 8)
        jitted = jax.jit(multi, donate_argnums=donate)
        fn = jitted
        if example_args is not None:
            t0 = time.perf_counter()
            prog = None
            if self._aot is not None:
                # store the donation-free export; donation is re-applied on
                # the wrapper below so one artifact serves CPU and TPU
                prog = self._aot.get_or_build(
                    self._aot_key("sim.multi_round",
                                  trees={"args": example_args},
                                  extra={"chunk": n, "donate": list(donate),
                                         "stacked": REAL_COUNT_KEY,
                                         "lane_buckets": self._lane_buckets}),
                    lambda: aotlib.export_program(jax.jit(multi), example_args),
                )
            # a failed compile propagates: swallowing it here used to defer
            # the error to the first dispatch, where it surfaced as a
            # misleading "carried state was donated" failure
            with traced("sim.chunk_compile", rounds=n, sink=self._otlp_sink):
                if prog is not None:
                    fn = prog.bind(example_args, donate_argnums=donate)
                else:
                    fn = jitted.lower(*example_args).compile()
                    # which scope each of its device ops is in (obs/scopes.py); a
                    # program bound from the AOT store publishes none
                    scopes.note_program("sim.chunk", fn)
            CHUNK_COMPILE_TIME.observe(time.perf_counter() - t0)
            if self._cost_gauges:
                cost = aotlib.record_program_cost(fn, f"sim.multi_round.{n}")
                if cost is not None:
                    self._chunk_flops[n] = cost["flops"]
        self._multi_round_fns[n] = fn
        return fn

    def _stage_scalar(self, x):
        """Explicitly place a per-round host scalar with the replicated
        sharding the compiled programs expect.  Staging it deliberately (an
        explicit ``device_put``, outside the TRACESAN round guard) keeps the
        dispatch itself transfer-free — a bare ``jnp.int32`` lands on one
        device and every mesh dispatch re-reshards it device-to-device."""
        if self.backend == C.SIMULATION_BACKEND_SP:
            return x
        return jax.device_put(x, meshlib.replicated(self.mesh))

    def run_rounds(self, n: int) -> list[dict]:
        """Run ``n`` rounds as one compiled program (mesh backend); falls back
        to the host loop per round on the SP backend.  Returns one metrics
        dict per round; the device is synced ONCE, at the end.

        The carried state is DONATED to the chunk (in-place HBM rewrite); if
        the chunk itself fails (OOM, device loss) the simulator's state
        buffers are gone — recover via ``try_resume`` from the last
        checkpoint, not by retrying in-process.

        Spans (``obs/trace.py``; PERF.md names the metric each is for):
        ``sim.run_rounds`` holds ``sim.stage`` (arguments, and the chunk
        program's ``sim.chunk_compile`` on its first use) and ``sim.chunk``
        (``sim.dispatch``, ``sim.metrics_sync``)."""
        with traced("sim.run_rounds", counters=XLA_COUNTERS, rounds=n,
                    start_round=self.round_idx) as span:
            if n <= 0:
                return []
            if self._population is not None:
                return self._run_population_rounds(n)
            if self.backend == C.SIMULATION_BACKEND_SP:
                out = []
                for _ in range(n):
                    t0 = time.perf_counter()
                    out.append(self.run_round())
                    ROUND_TIME.observe(time.perf_counter() - t0)
                return out
            return self._run_chunk(n, span)

    def _count_samples(self, metrics: dict, rounds: int) -> tuple[int, int]:
        """Feed ``fedml_sim_samples_total`` from what the round program said
        of its ``rounds`` rounds, taking its extra outputs out of ``metrics``:
        the sum of the sampled clients' counts and, from a bucketed program,
        the lane-steps its buckets ran (a program without buckets runs every
        lane to full capacity).  Returns (real, lane)."""
        hp = self.hp
        real = int(np.sum(metrics.pop(REAL_COUNT_KEY))) * hp.epochs
        lane_steps = metrics.pop(LANE_STEPS_KEY, None)
        if lane_steps is None:
            lane_steps = self._lanes * hp.steps_per_epoch * hp.epochs * rounds
        lane = int(np.sum(lane_steps)) * hp.batch_size
        SAMPLES.inc(real, kind="real")
        SAMPLES.inc(lane, kind="lane")
        return real, lane

    def _run_chunk(self, n: int, span) -> list[dict]:
        """The mesh path of ``run_rounds``; ``span`` is its ``sim.run_rounds``."""
        with traced("sim.stage"):
            args = (
                self.global_vars, self.server_state, self.client_states,
                self.counts, self._data[0], self._data[1],
                self._stage_scalar(jnp.int32(self.round_idx)), self.root_key,
                self.defense_history,
            )
            fn = self._get_multi_round_fn(n, example_args=args)
        t0 = time.perf_counter()
        try:
            with traced("sim.chunk", rounds=n, start_round=self.round_idx,
                        sink=self._otlp_sink):
                with traced("sim.dispatch"), tracesan.round_guard(self.round_idx, rounds=n):
                    gv, ss, cs, nd, stacked = fn(*args)
                with traced("sim.metrics_sync"), tracesan.allow("round_metrics"):
                    host = jax.device_get(stacked)  # graftlint: disable=GL010(annotated measurement site: THE single explicit host sync for the whole scanned chunk — n rounds of stacked metrics in one transfer)
        except Exception as e:
            raise RuntimeError(
                f"scanned chunk of {n} rounds failed at round {self.round_idx}; "
                "carried state was donated and is no longer valid — resume from "
                "the last checkpoint"
            ) from e
        execute_s = time.perf_counter() - t0
        CHUNK_EXECUTE_TIME.observe(execute_s)
        lane_buckets = self._lane_buckets if LANE_STEPS_KEY in host else 1
        real, lane = self._count_samples(host, n)
        span.attrs.update(real_samples=real, lane_samples=lane, lane_buckets=lane_buckets)
        if self._cost_gauges and self._chunk_flops.get(n):
            achieved = self._chunk_flops[n] / max(execute_s, 1e-9)
            ACHIEVED_FLOPS.set(achieved)
            peak = flopslib.local_peak_flops()
            if peak is not None:
                SIM_MFU.set(achieved / peak)
        for _ in range(n):
            ROUND_TIME.observe(execute_s / n)
        self.global_vars, self.server_state, self.client_states = gv, ss, cs
        if nd is not None:
            self.defense_history = nd
        self.round_idx += n
        return [{k: float(v[i]) for k, v in host.items()} for i in range(n)]

    # ------------------------------------------------------------------
    def run_round(self) -> dict:
        if self._population is not None:
            return self._run_population_rounds(1)[0]
        r = self.round_idx
        if self.backend == C.SIMULATION_BACKEND_SP:
            metrics = self._run_round_sp(r)
        else:
            # staged OUTSIDE the guard: uploading the round index is an
            # explicit (and replicated — see _stage_scalar) h2d per round
            r_dev = self._stage_scalar(jnp.int32(r))
            with tracesan.round_guard(r):
                gv, ss, cs, nd, metrics = self._round_fn(
                    self.global_vars, self.server_state, self.client_states,
                    self.counts, self._data[0], self._data[1],
                    r_dev, self.root_key, self.defense_history,
                )
            self.global_vars, self.server_state, self.client_states = gv, ss, cs
            if nd is not None:
                self.defense_history = nd
        self.round_idx += 1
        with tracesan.allow("round_metrics"):
            if self.backend != C.SIMULATION_BACKEND_SP:
                self._count_samples(metrics, 1)
            return {k: float(v) for k, v in metrics.items()}  # graftlint: disable=GL010(annotated measurement site: single-round entry point syncs its own metric dict — the chunked path run_rounds amortizes this to one sync per chunk)

    def _run_round_sp(self, r: int) -> dict:
        """Sequential reference twin: same sampling, same per-client keys, same
        aggregate — but a host loop like ``fedavg_api.py:88-103``."""
        cfg = self.cfg
        n_total = self.dataset.n_clients
        m = min(cfg.client_num_per_round, n_total)
        sampled = np.asarray(rng.sample_clients(self.root_key, r, n_total, m))
        rkey = rng.round_key(self.root_key, r)
        contribs, new_states, metrics_list = [], [], []
        for ci in sampled:
            k = rng.client_key(rkey, int(ci))
            cs = (
                jax.tree_util.tree_map(lambda s: s[int(ci)], self.client_states)
                if self.client_states is not None else None
            )
            x = self._data[0][int(ci)]
            y = self._data[1][int(ci)]
            contrib, new_cs, mt = self._client_fn_sp(
                self.global_vars, cs, self.server_state, x, y, self.counts[int(ci)], k
            )
            contribs.append(contrib)
            new_states.append(new_cs)
            metrics_list.append(mt)
        stacked = pt.tree_stack(contribs)
        weights = self.counts[sampled].astype(jnp.float32)
        self.global_vars, self.server_state, nd = self._server_path(
            stacked, weights, jnp.asarray(sampled, jnp.int32), self.global_vars,
            self.server_state, rkey, jnp.int32(r), self.defense_history,
        )
        if nd is not None:
            self.defense_history = nd
        if self.client_states is not None and new_states[0] is not None:
            for ci, ncs in zip(sampled, new_states):
                self.client_states = jax.tree_util.tree_map(
                    lambda full, upd: full.at[int(ci)].set(upd), self.client_states, ncs
                )
        stacked_m = pt.tree_stack(metrics_list)
        return {k: jnp.mean(v) for k, v in stacked_m.items()}

    # ------------------------------------------------------------------
    def evaluate(self) -> dict:
        t0 = time.perf_counter()
        with traced("sim.eval", counters=XLA_COUNTERS, round_idx=self.round_idx,
                    eval_batch=self._eval_bs, eval_steps=self._test[0].shape[0] // self._eval_bs,
                    sink=self._otlp_sink):
            with traced("sim.eval.dispatch"):
                res = self._eval_fn(self.global_vars, *self._test)
            with traced("sim.eval.sync"):
                out = {k: float(v) for k, v in res.items()}  # graftlint: disable=GL010(annotated measurement site: evaluation runs OFF the round loop at frequency_of_the_test cadence — its scalar sync never sits on the steady-state path)
        EVAL_TIME.observe(time.perf_counter() - t0)
        return out

    # -- checkpoint / resume (first-class, SURVEY.md §5; save/resume plumbing
    # from core.checkpoint.RoundCheckpointMixin) ------------------------------
    def _ckpt_state(self) -> dict:
        state = {
            "global_vars": self.global_vars,
            "server_state": self.server_state,
            "round_idx": self.round_idx,
            "root_key": self.root_key,
        }
        if self.client_states is not None:
            # store only the real clients — pad rows are a property of THIS
            # mesh; a resume may run on a different device count
            state["client_states"] = self._slice_lanes(self.client_states, self._n_real)
        if self.defense_history is not None:
            state["defense_history"] = self.defense_history
        return state

    def _apply_ckpt_state(self, state: dict) -> None:
        # re-apply the mesh placement __init__ establishes — restore hands
        # back host arrays, which would otherwise land unsharded on device 0
        self.global_vars = meshlib.replicate(state["global_vars"], self.mesh)
        self.server_state = jax.device_get(state["server_state"])
        self.server_state = meshlib.replicate(self.server_state, self.mesh)
        self.round_idx = int(state["round_idx"])
        # the checkpointed RNG key is authoritative (guards against a drifted
        # --random_seed silently changing the sampling stream mid-run)
        self.root_key = self._stage_scalar(jnp.asarray(state["root_key"]))
        if "client_states" in state:
            cs = meshlib.pad_leading_axis_np(state["client_states"], self._n_pad)
            self.client_states = meshlib.shard_leading_axis(cs, self.mesh)
        if "defense_history" in state:
            self.defense_history = jnp.asarray(state["defense_history"])

    def _next_boundary(self, r0: int) -> int:
        """First round index > r0 at which the host must intervene (eval,
        checkpoint, contribution snapshot, or the end of training); rounds in
        between run as one device-resident scanned chunk."""
        cfg = self.cfg
        ends = [cfg.comm_round]
        if cfg.frequency_of_the_test:
            f = cfg.frequency_of_the_test
            ends.append(((r0 // f) + 1) * f)
        if cfg.checkpoint_every_rounds:
            c = cfg.checkpoint_every_rounds
            ends.append(((r0 // c) + 1) * c)
        if getattr(cfg, "enable_contribution", False) and r0 < cfg.comm_round - 1:
            # a chunk must not straddle the last round: its pre-round state
            # gets snapshotted for contribution replay
            ends.append(cfg.comm_round - 1)
        return max(r0 + 1, min(e for e in ends if e > r0))

    def run(self) -> list[dict]:
        """The fit loop (reference ``FedAvgAPI.train`` ``fedavg_api.py:66``),
        executed in device-resident chunks between host boundaries."""
        history = []
        cfg = self.cfg
        self.try_resume()
        if self._aot is not None:
            self.warm_start()  # resolve every chunk program before round 0
        while self.round_idx < cfg.comm_round:
            r0 = self.round_idx
            if getattr(cfg, "enable_contribution", False) and r0 == cfg.comm_round - 1:
                # retain the pre-round state so contribution is assessed on
                # the ACTUAL last-round contributions (deterministic replay),
                # not fresh updates from the post-round global — reference
                # semantics (contribution_assessor_manager.py:9 assesses from
                # Context state captured during the round)
                self._contribution_snapshot = self._snapshot_pre_round(r0)
            end = self._next_boundary(r0)
            t0 = time.perf_counter()
            chunk = self.run_rounds(end - r0)
            span = time.perf_counter() - t0
            for i, metrics in enumerate(chunk):
                # chunk-average wall time: rounds inside one scanned chunk are
                # not individually timeable (and the first chunk's average
                # includes the scan program's compile); chunk_time_s is the
                # honest measured quantity
                metrics["round_time_s"] = span / len(chunk)
                metrics["chunk_time_s"] = span
                metrics["chunk_rounds"] = len(chunk)
                metrics["round"] = r0 + i
            r_last = r0 + len(chunk) - 1
            if cfg.frequency_of_the_test and (
                (r_last + 1) % cfg.frequency_of_the_test == 0 or r_last == cfg.comm_round - 1
            ):
                chunk[-1].update(self.evaluate())
            for metrics in chunk:
                self.logger.log(metrics)
                history.append(metrics)
            self.maybe_save_checkpoint(r_last)
        if getattr(cfg, "enable_contribution", False):
            scores = self.assess_contribution()
            if scores is not None:
                self.logger.log({f"contribution_c{i}": float(s) for i, s in enumerate(scores)})
        if self._otlp is not None:
            # end-of-fit egress: drain queued spans and ship the registry
            # snapshot; flush (not close) so a caller running fit again on
            # the same simulator keeps exporting
            self._otlp.export_metrics_now()
            self._otlp.flush(timeout=5.0)
        return history

    def _snapshot_pre_round(self, r: int) -> dict:
        # only the sampled clients' states are ever replayed (the sampled set
        # is deterministic in (root_key, r)), so don't host-copy the full
        # n_total stack — with SCAFFOLD-style per-client state that would be
        # n_total/m times more RAM than needed
        n_total = self.dataset.n_clients
        m = min(self.cfg.client_num_per_round, n_total)
        sampled = np.asarray(rng.sample_clients(self.root_key, r, n_total, m))
        return {
            "round": r,
            "global_vars": jax.device_get(self.global_vars),
            "server_state": jax.device_get(self.server_state),
            "client_states": (
                {
                    int(ci): jax.device_get(
                        jax.tree_util.tree_map(lambda s: s[int(ci)], self.client_states)
                    )
                    for ci in sampled
                }
                if self.client_states is not None else None
            ),
        }

    def last_round_contributions(self):
        """Deterministically replay the last round's EXACT client
        contributions from the retained pre-round snapshot: same sampled set,
        same round key, same pre-round global/server/client states as the
        round that was aggregated.  Returns (stacked, weights, sampled,
        snapshot) or None when no snapshot was retained."""
        snap = getattr(self, "_contribution_snapshot", None)
        if snap is None:
            return None
        r = snap["round"]
        n_total = self.dataset.n_clients
        m = min(self.cfg.client_num_per_round, n_total)
        sampled = np.asarray(rng.sample_clients(self.root_key, r, n_total, m))
        rkey = rng.round_key(self.root_key, r)
        fn = self._client_fn_sp or jax.jit(self._sp_client_update)
        contribs, weights = [], []
        for ci in sampled:
            cs = snap["client_states"][int(ci)] if snap["client_states"] is not None else None
            contrib, _, _ = fn(
                snap["global_vars"], cs, snap["server_state"],
                self._data[0][int(ci)], self._data[1][int(ci)],
                self.counts[int(ci)], rng.client_key(rkey, int(ci)),
            )
            contribs.append(contrib)
            weights.append(float(self.counts[int(ci)]))
        return pt.tree_stack(contribs), weights, sampled, snap

    def assess_contribution(self):
        """Shapley contribution of the last round's sampled clients
        (reference ``ServerAggregator.assess_contribution``
        ``server_aggregator.py:105``): scores the coalitions of the ACTUAL
        last-round contributions (replayed from the pre-round snapshot) by
        test accuracy."""
        from ..trust.contribution import ContributionAssessorManager

        mgr = ContributionAssessorManager(self.cfg)
        if not mgr.enabled or self.round_idx == 0:
            return None
        replay = self.last_round_contributions()
        if replay is None:
            return None
        stacked, weights, sampled, snap = replay
        one = jax.tree_util.tree_map(lambda x: x[0], stacked)
        if jax.tree_util.tree_structure(one) != jax.tree_util.tree_structure(self.global_vars):
            return None  # contribution defined on weight-style contributions

        def eval_fn(agg_vars):
            return self._eval_fn(agg_vars, *self._test)["test_acc"]

        return mgr.assess(stacked, np.asarray(weights), eval_fn, empty_model=snap["global_vars"])
