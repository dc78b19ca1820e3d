"""Driver of the Kimi-Linear cells: adapter fine-tuning over a frozen base on
one expert-parallel rank, ``LLMTrainer(cfg, args, mesh).fit(batches)`` with
``lora_rank`` set.

The adapter cells' driver (``sala.py``) with this configuration's model
(Kimi Delta Attention in four of five layers beside latent attention without
positions; a dense first layer, then expert layers whose sigmoid router
chooses by a selection bias, one rank's experts held, one shared expert),
its base and adapters from the seed and its float32 reference
(``ref_kimi.py``), and its required work (``flops_kimi.py``).  Beside the
three gaps it records the assignments on held experts at step 1 (the
program's summed over its layers, the reference's by layer) and the KDA
layers' ``kda_chunk_decay``.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from flax import traverse_util

import compare
import flops_kimi
import ref_kimi
import sala

FIRST_STEPS = sala.FIRST_STEPS


def transformer_config(c: dict, seq_len: int, remat_policy: str = "full", **overrides):
    """The program's ``TransformerConfig`` of a configuration file."""
    from fedml_tpu.models.transformer import TransformerConfig

    ref_kimi.sizes(c)   # refuses what neither program nor reference has
    lac = c["linear_attn_config"]
    return TransformerConfig(**{**dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], max_seq_len=seq_len, rope_theta=float(c["rope_theta"]),
        norm_eps=c["rms_norm_eps"], dtype=jnp.bfloat16, remat=True, remat_policy=remat_policy,
        logits_dtype=jnp.bfloat16, mixer_types=tuple(kind for _, kind, _ in flops_kimi.layers(c)),
        kda_heads=lac["num_heads"], kda_head_dim=lac["head_dim"], kda_conv=lac["short_conv_kernel_size"],
        q_lora_rank=0, kv_lora_rank=c["kv_lora_rank"], qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"], mla_use_nope=True,
        first_k_dense=c["first_k_dense_replace"], n_routed_experts=c["router_experts"],
        experts_held=c["num_experts"], first_expert=c["first_expert"], top_k=c["num_experts_per_token"],
        n_shared_experts=c["num_shared_experts"], moe_d_ff=c["moe_intermediate_size"],
        routed_scaling_factor=c["routed_scaling_factor"], norm_topk_prob=c["moe_renormalize"],
        router_scoring="sigmoid", router_bias=True), **overrides})


class Driver(sala.Driver):
    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int, devices):
        super().__init__(cell, config, traffic, seed, devices)
        flops_kimi.check()  # the yardstick's counts, before anything is measured with them

    # ------------------------------------------------------------- set-up
    def build(self) -> None:
        from fedml_tpu.llm.train import LLMTrainArgs, LLMTrainer
        from fedml_tpu.parallel import mesh as meshlib, sharding

        c, t = self.c, self.t
        cfg = transformer_config(c, self.seq, t.get("remat_policy", "full"), **t.get("program", {}))
        args = LLMTrainArgs(batch_size=self.batch, seq_len=self.seq, seed=0, **self.a)
        mesh = meshlib.make_mesh((meshlib.AXIS_DATA,), devices=self.devices)
        t0 = time.perf_counter()
        self.trainer = tr = LLMTrainer(cfg, args, mesh=mesh)
        jax.block_until_ready(tr.opt_state)
        self.marks = [("LLMTrainer", time.perf_counter() - t0)]
        # the benchmark's base and adapters from the seed, in the trainer's own
        # placement; the trainer's own draws are dropped first
        for leaf in jax.tree_util.tree_leaves((tr.params, tr.lora)):
            leaf.delete()
        shardings = compare.flat(tr.param_shardings)
        w = ref_kimi.drawn_weights(c, self.seed)
        if sorted(w) != sorted(shardings):
            raise ValueError("the reference's leaves are not the program's")
        jax.block_until_ready(w)
        self.marks.append(("weights_drawn", time.perf_counter() - t0))
        # init_weights' two steps, timed apart: the calibration of the
        # selection biases is a forward of the reference over a whole row
        w.update(ref_kimi.balanced_biases(w, c, self.seed))
        jax.block_until_ready(w)
        self.marks.append(("selection_biases_balanced", time.perf_counter() - t0))
        tr.params = traverse_util.unflatten_dict({k: jax.device_put(v, shardings[k]) for k, v in w.items()},
                                                 sep="/")
        # placed as the step returns them: an adapter tree that arrives under
        # another sharding type makes the step's second call compile again
        lora = sala.program_adapters(ref_kimi.init_adapters(c, self.a, self.seed))
        tr.lora = jax.device_put(lora, sharding.named_shardings(lora, mesh))
        jax.block_until_ready((tr.params, tr.lora))
        self.marks.append(("weights_from_seed", time.perf_counter() - t0))
        self._step_program = tr._train_step

    def first_steps(self) -> dict:
        """Steps 1..3 through ``fit``; step 1 compiles (or loads).  Records
        the program's readings for ``check``."""
        tr = self.trainer
        t0 = time.perf_counter()
        h1 = self._fit(self._batches(count=1))
        first_s = time.perf_counter() - t0
        self.marks.append(("first_step", first_s))
        mu = {k.split("/mu/", 1)[1]: v for k, v in compare.flat(tr.opt_state).items() if "/mu/" in k}
        grad_norms = {k: v / (1.0 - ref_kimi.B1) for k, v in ref_kimi.leaf_norms(mu).items()}
        h23 = self._fit(self._batches(count=FIRST_STEPS - 1))
        self.marks.append(("steps_2_3", time.perf_counter() - t0))
        change = ref_kimi.change_norms(self.c, self.a, self.seed, self._adapters())
        # one more step so that the window's first finds the step program loaded again
        self._fit(self._batches(count=1))
        self.readings = {"losses": [h["loss"] for h in h1 + h23],
                         "grad_norms": grad_norms, "change_norms": change,
                         "held_in_step": h1[0]["moe_held"], "max_load_in_step": h1[0]["moe_max_load"],
                         "kda_chunk_decay": h1[0]["kda_chunk_decay"],
                         "attention_sites": dict(tr.attention_sites)}
        steady = min(h["step_time_s"] for h in h23)
        return {"first_call_s": first_s, "steady_s": steady}

    # ------------------------------------------------------------- window
    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            hist = self._fit(self._batches(deadline=t0 + seconds))
            clock = time.perf_counter() - t0
        c, n, b = self.c, len(hist), self.batch
        t = b * self.seq
        kinds = flops_kimi.layers(c)
        expert_layers = sum(experts for _, _, experts in kinds)
        kda_layers = sum(kind == "kda" for _, kind, _ in kinds)
        attention = [flops_kimi.mla_attention_work(c, b, self.seq)]
        kernel, mla_layers = self.on_kernel(), len(kinds) - kda_layers
        # the rows the held experts REALLY saw in the window, spread evenly
        # over its steps and expert layers
        held = sum(h["moe_held"] for h in hist) / max(n * expert_layers, 1)
        return {
            "work": float(t * n), "clock_s": clock, "attempted": n, "failed": 0,
            "pieces_s": [h["step_time_s"] for h in hist], "piece": "step",
            "flops_required": n * flops_kimi.train_flops_per_step(c, self.job, b, self.seq),
            "roofline_work": {
                "matmul": [(flops_kimi.step_matmuls(c, self.job, b, self.seq, attention=False), n),
                           (attention, n * mla_layers * (1 - kernel))],
                "flash": [(attention, n * mla_layers * kernel)],
                "moe": [(flops_kimi.moe_products(c, t, held), n * expert_layers)],
                "kda": [([flops_kimi.kda_work(c, b, self.seq)], n * kda_layers)]},
            "losses": [h["loss"] for h in hist],
            # the routing each step saw: a step's rounds follow its busiest held expert
            "moe_held": [h["moe_held"] for h in hist],
            "moe_max_load": [h["moe_max_load"] for h in hist],
            "kda_chunk_decay": [h["kda_chunk_decay"] for h in hist],
        }

    # -------------------------------------------------------------- check
    def reference(self, control=None, fault=None) -> dict:
        """The reference's readings, with its assignments on held experts by
        layer at step 1."""
        ref = ref_kimi.ReferenceTrainer(self.c, self.a, self.seed, control=control)
        losses, grad_norms, held = [], None, None
        for s in range(FIRST_STEPS):
            tok, tgt = ref_kimi.batch_tokens(self.seed, s, self.batch, self.seq, self.c["vocab_size"], fault)
            r = ref.step(tok, tgt)
            losses.append(r["loss"])
            if s == 0:
                grad_norms, held = r["grad_norms"], list(ref.held)
        return {"losses": losses, "grad_norms": grad_norms, "change_norms": ref.change_norms(),
                "held_by_block": held}
