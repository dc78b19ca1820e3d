"""LLM trainer — pjit-sharded next-token training.

Capability target: the reference's ``train/llm`` stack (HF Trainer +
DeepSpeed ZeRO-3 + bf16, ``hf_trainer.py``, ``distributed.py:21-68``) and the
TensorOpera-Train "Llama-3 distributed pretrain" config (BASELINE.md).
TPU-native: one jitted train step over a (data, model, seq) mesh — ZeRO-3 is
the parameter sharding rules (``parallel/sharding.py``), tensor parallelism
is the model axis, ring attention the seq axis; AdamW + cosine schedule +
grad clipping mirror the reference's TrainingArguments defaults; perplexity
logging matches ``hf_trainer.py``'s metric.

With ``lora_rank`` the trainer fine-tunes adapters over a frozen base (the
reference's HF Trainer + PEFT LoRA): the base is held in the model's dtype
with the parameters' shardings (2 bytes a parameter, no gradient, no moments),
the adapters (``llm/lora.py``) and their AdamW state in float32; the step
differentiates with respect to the adapters only and takes the base as an
argument it neither donates nor returns.

A batch is two arrays ``(tokens, targets)`` or, for packed documents
(``llm/packing.py``), three: ``(tokens, targets, segments)``.  With
``segments`` the model keeps every mixer inside a document and the loss is
the mean over the positions whose target lies in their own document; that
step is a program of its own, compiled on its first call, and two-array
batches run the program they always ran.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..core import rng
from ..models.transformer import KDA_STATS, MOE_STATS, Transformer, TransformerConfig, targets_in_document
from ..obs import scopes
from ..obs.metrics import MetricsLogger
from ..obs.trace import (LLM_ATTENDED_KEYS, LLM_EXPERT_TOKENS, LLM_LOSS_TOKENS, LLM_PACKED_DOCUMENTS,
                         XLA_COUNTERS, install_xla_listener, traced)
from ..ops.kda import KDA_PATHS, kda_sites
from ..ops.sparse_attention import ATTENTION_PATHS, attention_sites
from ..ops.ssd import SCAN_PATHS, scan_sites
from ..parallel import mesh as meshlib, sharding
from . import lora as lora_lib

#: what a model with block-sparse layers reports beside its loss: the keys
#: its sparse layers attended and those a causal layer would have
ATTENDED = ("sparse_kept", "sparse_causal")
#: what a step on packed rows reports beside its loss: the batch's documents,
#: the positions its loss counts, the causal pairs (query, key at or before
#: it) inside documents, and those of the whole rows
PACKED = ("docs", "loss_tokens", "doc_pairs", "causal_pairs")


def packed_stats(segments) -> dict:
    """``PACKED`` of a batch's ``segments`` (b, s), summed on the device (a
    row's sums in int32, the batch's in float32)."""
    b, s = segments.shape
    real = segments != 0
    starts = real & jnp.pad(segments[:, 1:] != segments[:, :-1], ((0, 0), (1, 0)), constant_values=True)
    at = jnp.arange(s, dtype=jnp.int32)
    since_start = at - jax.lax.cummax(jnp.where(starts, at, 0), axis=1)
    total = lambda t: jnp.sum(jnp.sum(t.astype(jnp.int32), axis=1).astype(jnp.float32))
    return {"docs": total(starts), "loss_tokens": total(targets_in_document(segments)),
            "doc_pairs": total(jnp.where(real, since_start + 1, 0)),
            "causal_pairs": jnp.float32(b * (s * (s + 1) // 2))}


@dataclass(frozen=True)
class LLMTrainArgs:
    """Reference ``ExperimentArguments(TrainingArguments)`` essentials
    (``train/llm/configurations.py:32``)."""

    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    batch_size: int = 8
    seq_len: int = 512
    seed: int = 0
    # adapter fine-tuning over a frozen base (llm/lora.py); 0 trains everything
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: str = lora_lib.DEFAULT_TARGETS
    # weight of the multi-token-prediction loss, where the model has such a
    # module (``TransformerConfig.mtp_layers``): loss = main + mtp_weight * mtp
    mtp_weight: float = 0.3


class LLMTrainer:
    @traced("llm.init")
    def __init__(self, cfg: TransformerConfig, args: LLMTrainArgs,
                 mesh=None, seq_axis: Optional[str] = None,
                 logger: Optional[MetricsLogger] = None):
        install_xla_listener()  # this entry point skips fedml_tpu.init
        self.cfg = cfg
        self.args = args
        if mesh is None:
            mesh = meshlib.make_mesh((meshlib.AXIS_DATA,))
        self.mesh = mesh
        self.seq_axis = seq_axis if (seq_axis and seq_axis in mesh.shape and mesh.shape[seq_axis] > 1) else None
        # a model that may compute on sharded arrays holds the mesh: its
        # mixers then stay off kernels that jax cannot partition
        self.model = Transformer(cfg, mesh=mesh if self.seq_axis or mesh.size > 1 else None,
                                 seq_axis=self.seq_axis)
        self.logger = logger or MetricsLogger()

        k0 = rng.root_key(args.seed)
        sample = jnp.zeros((args.batch_size, args.seq_len), jnp.int32)
        variables = jax.eval_shape(lambda: self.model.init({"params": k0}, sample))
        # materialize params directly into their shardings (no host spike)
        self.param_shardings = sharding.named_shardings(variables["params"], mesh)

        adapters = args.lora_rank > 0

        def init_fn():
            params = self.model.init({"params": k0}, sample)["params"]
            if adapters:  # frozen: held in the model's dtype
                params = jax.tree_util.tree_map(lambda x: x.astype(cfg.dtype), params)
            return params

        with traced("llm.init.params"):
            self.params = jax.jit(
                init_fn, out_shardings=self.param_shardings
            )()

        schedule = optax.warmup_cosine_decay_schedule(
            0.0, args.learning_rate, args.warmup_steps, max(args.total_steps, args.warmup_steps + 1)
        )
        self.opt = optax.chain(
            optax.clip_by_global_norm(args.grad_clip),
            optax.adamw(schedule, b1=0.9, b2=0.95, weight_decay=args.weight_decay),
        )
        # Optimizer moments must NOT inherit shardings by propagation: optax
        # init builds them as zeros with no data dependence on the params, so
        # XLA places them on device 0 (SingleDeviceSharding) — a multi-device
        # step then rejects the mixed device set.  The moment paths end with
        # the param path ('...nu/layer_0/attn/wq/kernel'), so the same
        # path-regex rules shard them like their params; scalars (count)
        # fall through to the replicate-by-default rule.
        self.lora = None
        trained_shardings = self.param_shardings
        if adapters:
            with traced("llm.init.adapters"):
                make = lambda: lora_lib.init_lora(
                    variables["params"], args.lora_rank, jax.random.fold_in(k0, 1),
                    targets=args.lora_targets)
                # no rule names an adapter's path: replicated, like the norms
                trained_shardings = sharding.named_shardings(jax.eval_shape(make), mesh)
                self.lora = jax.jit(make, out_shardings=trained_shardings)()
        with traced("llm.init.opt"):
            trained = self.params if self.lora is None else self.lora
            opt_shardings = sharding.named_shardings(
                jax.eval_shape(self.opt.init, trained), mesh
            )
            self.opt_state = jax.jit(self.opt.init, out_shardings=opt_shardings)(trained)
        self.data_sharding = sharding.batch_sharding(mesh, seq_axis=self.seq_axis)
        self.step_idx = 0
        #: the step program's blockwise-attention call sites by path, known
        #: once the program has been traced (its first call)
        self.attention_sites = dict.fromkeys(ATTENTION_PATHS, 0)
        #: ... and its selective-scan call sites, likewise
        self.scan_sites = dict.fromkeys(SCAN_PATHS, 0)
        #: ... and its KDA call sites, likewise
        self.kda_sites = dict.fromkeys(KDA_PATHS, 0)
        #: the step program last published to ``obs/scopes.py`` (``_dispatch``)
        self._noted_step = None
        # Pin the step's output shardings to the input shardings: with
        # donation and unspecified out_shardings, XLA may pick different
        # layouts for the outputs, and the SECOND call then recompiles
        # against the new input layouts (a silent ~80 s hit on real chips).
        scalar_sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        jit_step = lambda packed: jax.jit(
            self._make_train_step(),
            donate_argnums=(0, 1),
            out_shardings=(trained_shardings, opt_shardings,
                           {k: scalar_sh for k in self._metric_names(packed)}),
        )
        self._train_step = jit_step(False)
        #: the step on packed rows (a third array, ``segments``)
        self._train_step_packed = jit_step(True)

    def _sown_names(self) -> tuple:
        """What the model's layers sow into collection ``stats``."""
        return ((ATTENDED if self.cfg.has_sparse_layers else ())
                + (MOE_STATS if self.cfg.has_expert_layers else ())
                + (KDA_STATS if self.cfg.has_kda_layers else ()))

    def _metric_names(self, packed: bool = False) -> tuple:
        return (("loss", "ppl") + self._sown_names() + (("mtp_loss",) if self.cfg.mtp_layers else ())
                + (PACKED if packed else ()))

    def _make_train_step(self):
        """``(params, opt_state, tokens, targets)``, or with adapters
        ``(lora, opt_state, base, tokens, targets)``; the first two are
        donated and come back updated, with the step's metrics.  Packed rows
        bring one more array at the end, ``segments``, and ``PACKED`` among
        the metrics."""
        model = self.model
        opt = self.opt
        args = self.args
        # the step writes its sites into the trainer's two dicts, not into the
        # trainer: the jitted step outlives it (``obs/scopes.py`` keeps the step)
        attn_sites, scans, kdas = self.attention_sites, self.scan_sites, self.kda_sites
        sown_names = self._sown_names()
        mtp = self.cfg.mtp_layers > 0
        # the MTP module's loss needs the targets inside the model too
        chunked = self.cfg.loss_chunk > 0 or mtp

        def loss_fn(trained, base, tokens, targets, segments=None):
            variables = {"params": trained} if base is None else {
                "params": base,
                "lora": lora_lib.as_collection(trained, args.lora_alpha, args.lora_rank)}
            stats = {}
            # with a loss_chunk the model takes the targets and returns the
            # per-token losses: the whole logits matrix never exists
            kw = {"targets": targets} if chunked or segments is not None else {}
            if segments is not None:   # the model's per-token losses are 0 where they do not count
                kw["segments"] = segments
                stats.update(packed_stats(segments))
            if sown_names:  # sparse layers sow what they attended, expert layers their routing
                out, sown = model.apply(variables, tokens, train=True, mutable=["stats"], **kw)
                for name in sown_names:
                    stats[name] = sum(v for path, v in jax.tree_util.tree_leaves_with_path(sown)
                                      if path[-1].key == name)
            else:
                out = model.apply(variables, tokens, train=True, **kw)
            if mtp:  # the last position has no token after next: the mean is over the others
                out, after_next = out
                stats["mtp_loss"] = after_next.sum() / (after_next.size - after_next.shape[0])
                return out.mean() + args.mtp_weight * stats["mtp_loss"], stats
            if segments is not None:
                return out.sum() / jnp.maximum(stats["loss_tokens"], 1.0), stats
            if chunked:
                return out.mean(), stats
            with jax.named_scope("llm.head_loss"):
                losses = optax.softmax_cross_entropy_with_integer_labels(
                    out.astype(jnp.float32), targets
                )
                return losses.mean(), stats

        def update(trained, opt_state, base, tokens, targets, *segments):
            # runs while the program is traced: the sites counted meanwhile are its own
            before, scans_before, kdas_before = attention_sites(), scan_sites(), kda_sites()
            # the scopes name each op's phase in a device profile (XProf)
            with jax.named_scope("llm.fwd_bwd"):
                (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    trained, base, tokens, targets, *segments)
            attn_sites.update({p: n - before[p] for p, n in attention_sites().items()})
            scans.update({p: n - scans_before[p] for p, n in scan_sites().items()})
            kdas.update({p: n - kdas_before[p] for p, n in kda_sites().items()})
            with jax.named_scope("llm.optimizer"):
                updates, opt_state = opt.update(grads, opt_state, trained)
                trained = optax.apply_updates(trained, updates)
            main = loss - args.mtp_weight * stats["mtp_loss"] if mtp else loss
            return trained, opt_state, {"loss": loss, "ppl": jnp.exp(main), **stats}

        if self.lora is None:
            return lambda params, opt_state, *batch: update(params, opt_state, None, *batch)
        return update

    def _dispatch(self, *batch) -> dict:
        """One call of the step program on the trainer's own state: of the
        packed one where the batch has ``segments``."""
        step = self._train_step if len(batch) == 2 else self._train_step_packed
        args = ((self.params, self.opt_state, *batch) if self.lora is None
                else (self.lora, self.opt_state, self.params, *batch))
        if step is not self._noted_step:  # once a program: which scope each of its device ops is in
            self._noted_step = step
            if hasattr(step, "lower"):
                scopes.note_program("llm.step", step, args)
        trained, self.opt_state, metrics = step(*args)
        if self.lora is None:
            self.params = trained
        else:
            self.lora = trained
        return metrics

    def step(self, tokens: jax.Array, targets: jax.Array, segments=None) -> dict:
        batch = (tokens, targets) if segments is None else (tokens, targets, segments)
        with traced("llm.h2d"):
            batch = tuple(jax.device_put(x, self.data_sharding) for x in batch)
        with traced("llm.dispatch"):
            metrics = self._dispatch(*batch)
        self.step_idx += 1
        with traced("llm.sync"):
            return {k: float(v) for k, v in metrics.items()}

    def fit(self, batch_iter, steps: Optional[int] = None) -> list[dict]:
        """Spans (``obs/trace.py``; PERF.md names the metric each is for):
        ``llm.fit`` holds, per step, ``llm.next_batch`` (the caller's
        iterator), ``llm.step`` (what ``step_time_s`` times: ``llm.h2d``,
        ``llm.dispatch``, ``llm.sync``) and ``llm.log``.  A model with
        block-sparse layers also says what they attended: ``sparse_kept`` and
        ``sparse_causal`` in each history entry, as attributes of ``llm.step``
        and in ``fedml_llm_attended_keys_total``; one with expert layers how
        its tokens were routed: ``moe_assignments``, ``moe_held`` and
        ``moe_max_load`` likewise, and ``fedml_llm_expert_tokens_total``.
        ``llm.fit`` says on which path the step program's blockwise-attention
        call sites were built: ``attn_kernel_sites``, ``attn_blockwise_sites``
        (``fedml_llm_attention_sites_total`` counts every traced program's),
        and its selective-scan sites: ``scan_kernel_sites``, ``scan_scan_sites``
        (``fedml_llm_scan_sites_total``), and its KDA sites:
        ``kda_kernel_sites``, ``kda_scan_sites`` (``fedml_llm_kda_sites_total``);
        a model with KDA layers ``kda_chunk_decay`` (``KDA_STATS``) in each
        history entry and on ``llm.step``.
        A batch of three arrays is packed rows: ``docs``, ``loss_tokens``,
        ``doc_pairs`` and ``causal_pairs`` in each history entry and on
        ``llm.step``, ``fedml_llm_packed_documents_total``,
        ``fedml_llm_loss_tokens_total`` and, for the model's softmax layers,
        ``fedml_llm_attended_keys_total`` (kept = pairs inside documents)."""
        history = []
        steps = steps or self.args.total_steps
        batches = iter(batch_iter)
        # a packed step's pairs are summed over KV heads and softmax layers, as the sparse layers' are
        softmax_heads = self.cfg.n_kv_heads * sum(
            self.cfg.mixer(i) == "attention" for i in range(self.cfg.n_layers))
        with traced("llm.fit", counters=XLA_COUNTERS) as fit_span:
            for i in range(steps + 1):
                with traced("llm.next_batch"):
                    batch = next(batches, None)
                if batch is None or i >= steps:
                    break
                with traced("llm.step", step=self.step_idx + 1) as span:
                    t0 = time.perf_counter()
                    m = self.step(*batch)
                    m["step"] = self.step_idx
                    m["step_time_s"] = time.perf_counter() - t0
                    if ATTENDED[0] in m:
                        span.attrs.update({k: m[k] for k in ATTENDED})
                        for name, kind in zip(ATTENDED, ("kept", "causal")):
                            LLM_ATTENDED_KEYS.inc(m[name], kind=kind)
                    if MOE_STATS[0] in m:
                        span.attrs.update({k: m[k] for k in MOE_STATS})
                        for name, kind in zip(MOE_STATS, ("routed", "held")):
                            LLM_EXPERT_TOKENS.inc(m[name], kind=kind)
                    if KDA_STATS[0] in m:
                        span.attrs.update({k: m[k] for k in KDA_STATS})
                    if PACKED[0] in m:
                        span.attrs.update({k: m[k] for k in PACKED}, tokens=float(batch[0].size))
                        LLM_PACKED_DOCUMENTS.inc(m["docs"])
                        LLM_LOSS_TOKENS.inc(m["loss_tokens"], kind="counted")
                        LLM_LOSS_TOKENS.inc(batch[0].size - m["loss_tokens"], kind="masked")
                        LLM_ATTENDED_KEYS.inc(m["doc_pairs"] * softmax_heads, kind="kept")
                        LLM_ATTENDED_KEYS.inc(m["causal_pairs"] * softmax_heads, kind="causal")
                with traced("llm.log"):
                    self.logger.log(m)
                history.append(m)
            fit_span.attrs.update({f"attn_{p}_sites": n for p, n in self.attention_sites.items()})
            fit_span.attrs.update({f"scan_{p}_sites": n for p, n in self.scan_sites.items()})
            fit_span.attrs.update({f"kda_{p}_sites": n for p, n in self.kda_sites.items()})
        return history

    def n_params(self) -> int:
        return sum(x.size for x in jax.tree_util.tree_leaves(self.params))

    def token_throughput(self, steps: int = 5) -> float:
        """tokens/sec on synthetic data (bench helper).

        Two warmup steps (first compile + any layout settle), then ``steps``
        back-to-back device steps with a single host sync at the end, so
        the per-step host round trip is not in the measured window.
        """
        a = self.args
        key = jax.random.PRNGKey(0)
        tokens = jax.random.randint(key, (a.batch_size, a.seq_len), 0, self.cfg.vocab_size)
        targets = jnp.roll(tokens, -1, axis=1)
        tokens = jax.device_put(tokens, self.data_sharding)
        targets = jax.device_put(targets, self.data_sharding)
        for _ in range(2):  # warmup: compile + layout settle
            float(self._dispatch(tokens, targets)["loss"])
        t0 = time.perf_counter()
        for _ in range(steps):
            m = self._dispatch(tokens, targets)
        float(m["loss"])  # host sync
        dt = time.perf_counter() - t0
        return a.batch_size * a.seq_len * steps / dt
