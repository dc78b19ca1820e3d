"""Driver of the openPangu-Ultra-MoE cells: adapter fine-tuning over a frozen
base on one expert-parallel rank, ``LLMTrainer(cfg, args, mesh).fit(batches)``
with ``lora_rank`` set.

The adapter cells' driver (``sala.py``) with this configuration's model
(latent attention, expert layers, sandwich norms, an MTP module), its base and
adapters from the seed and its float32 reference (``ref_pangu.py``), and its
required work (``flops_pangu.py``).  Beside the three gaps it records, for
program and reference, the assignments that landed on held experts in each
block at step 1.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from flax import traverse_util

import compare
import flops_pangu
import ref_pangu
import sala

FIRST_STEPS = sala.FIRST_STEPS


def transformer_config(c: dict, seq_len: int, remat_policy: str = "full", **overrides):
    """The program's ``TransformerConfig`` of a configuration file."""
    from fedml_tpu.models.transformer import TransformerConfig

    if not c["sandwich_norm"] or c["num_key_value_heads"] != c["num_attention_heads"] or c["attention_bias"]:
        raise ValueError("the program's latent attention has a key per head, no bias and sandwich norms")
    return TransformerConfig(**{**dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], max_seq_len=seq_len, rope_theta=float(c["rope_theta"]),
        norm_eps=c["rms_norm_eps"], dtype=jnp.bfloat16, remat=True, remat_policy=remat_policy,
        logits_dtype=jnp.bfloat16, mixer_types=("mla",) * c["num_hidden_layers"],
        q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"], qk_rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], first_k_dense=c["first_k_dense_replace"],
        n_routed_experts=c["router_experts"], experts_held=c["n_routed_experts"],
        first_expert=c["first_expert"], top_k=c["num_experts_per_tok"],
        n_shared_experts=c["n_shared_experts"], moe_d_ff=c["moe_intermediate_size"],
        routed_scaling_factor=c["routed_scaling_factor"], norm_topk_prob=c["norm_topk_prob"],
        sandwich_norm=True, mtp_layers=c["num_nextn_predict_layers"]), **overrides})


class Driver(sala.Driver):
    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int, devices):
        super().__init__(cell, config, traffic, seed, devices)
        flops_pangu.check()  # the yardstick's counts, before anything is measured with them

    # ------------------------------------------------------------- set-up
    def build(self) -> None:
        from fedml_tpu.llm.train import LLMTrainArgs, LLMTrainer
        from fedml_tpu.parallel import mesh as meshlib

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
        w = ref_pangu.init_weights(c, self.seed, compare.flat(tr.param_shardings))
        if sorted(w) != sorted(compare.flat(tr.param_shardings)):
            raise ValueError("the reference's leaves are not the program's")
        tr.params = traverse_util.unflatten_dict(w, sep="/")
        tr.lora = sala.program_adapters(ref_pangu.init_adapters(c, self.a, self.seed))
        jax.block_until_ready((tr.params, tr.lora))
        self.marks.append(("weights_from_seed", time.perf_counter() - t0))
        self._step_program = tr._train_step

    def _held_by_block(self) -> list[float]:
        """Assignments on held experts in each block (the layers in order,
        then the MTP module's) for step 1's batch under the first adapters:
        the model's forward alone, its ``stats`` left apart by block (the step
        program sums them)."""
        from fedml_tpu.llm import lora as lora_lib

        tr = self.trainer
        tok, tgt = ref_pangu.batch_tokens(self.seed, 0, self.batch, self.seq, self.c["vocab_size"],
                                          self.fault)

        def forward(base, lora, tok, tgt):
            variables = {"params": base, "lora": lora_lib.as_collection(
                lora, tr.args.lora_alpha, tr.args.lora_rank)}
            _, sown = tr.model.apply(variables, tok, train=True, targets=tgt, mutable=["stats"])
            return {path[:-len("/moe/moe_held")]: v
                    for path, v in compare.flat(sown["stats"]).items() if path.endswith("/moe_held")}

        held = jax.jit(forward)(tr.params, tr.lora, *(jax.device_put(x, tr.data_sharding)
                                                      for x in (tok, tgt)))
        order = [p.rstrip("/") for p, experts in flops_pangu.blocks(self.c) if experts]
        return [float(held[p]) for p in order]

    def first_steps(self) -> dict:
        """Steps 1..3 through ``fit``; step 1 compiles (or loads).  Records
        the program's readings for ``check``."""
        tr = self.trainer
        t0 = time.perf_counter()
        held = self._held_by_block()
        self.marks.append(("held_by_block", time.perf_counter() - t0))
        t0 = time.perf_counter()
        h1 = self._fit(self._batches(count=1))
        first_s = time.perf_counter() - t0
        self.marks.append(("first_step", first_s))
        mu = {k.split("/mu/", 1)[1]: v for k, v in compare.flat(tr.opt_state).items() if "/mu/" in k}
        grad_norms = {k: v / (1.0 - ref_pangu.B1) for k, v in ref_pangu.leaf_norms(mu).items()}
        h23 = self._fit(self._batches(count=FIRST_STEPS - 1))
        self.marks.append(("steps_2_3", time.perf_counter() - t0))
        change = ref_pangu.change_norms(self.c, self.a, self.seed, self._adapters())
        # one more step so that the window's first finds the step program loaded again
        self._fit(self._batches(count=1))
        self.readings = {"losses": [h["loss"] for h in h1 + h23],
                         "mtp_losses": [h["mtp_loss"] for h in h1 + h23],
                         "grad_norms": grad_norms, "change_norms": change,
                         "held_by_block": held, "held_in_step": h1[0]["moe_held"],
                         "max_load_in_step": h1[0]["moe_max_load"]}
        steady = min(h["step_time_s"] for h in h23)
        return {"first_call_s": first_s, "steady_s": steady}

    # ------------------------------------------------------------- window
    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            hist = self._fit(self._batches(deadline=t0 + seconds))
            clock = time.perf_counter() - t0
        c, n, t = self.c, len(hist), self.batch * self.seq
        blocks = flops_pangu.blocks(c)
        expert_blocks = sum(experts for _, experts in blocks)
        attention = [flops_pangu.attention_work(c, self.batch, self.seq)]
        kernel = self.on_kernel()
        # the rows the held experts REALLY saw in the window, spread evenly
        # over its steps and expert blocks
        held = sum(h["moe_held"] for h in hist) / max(n * expert_blocks, 1)
        return {
            "work": float(t * n), "clock_s": clock, "attempted": n, "failed": 0,
            "pieces_s": [h["step_time_s"] for h in hist], "piece": "step",
            "flops_required": n * flops_pangu.train_flops_per_step(c, self.job, self.batch, self.seq),
            "roofline_work": {
                "matmul": [(flops_pangu.step_matmuls(c, self.job, self.batch, self.seq, attention=False), n),
                           (attention, n * len(blocks) * (1 - kernel))],
                "flash": [(attention, n * len(blocks) * kernel)],
                "mla": [([flops_pangu.mla_work(c, self.batch, self.seq)], n * len(blocks))],
                "moe": [(flops_pangu.moe_products(c, t, held), n * expert_blocks)]},
            "losses": [h["loss"] for h in hist],
            # the routing each step saw: a step's rounds follow its busiest held expert
            "moe_held": [h["moe_held"] for h in hist],
            "moe_max_load": [h["moe_max_load"] for h in hist],
        }

    # -------------------------------------------------------------- check
    def reference(self, control=None, fault=None) -> dict:
        ref = ref_pangu.ReferenceTrainer(self.c, self.a, self.seed, control=control)
        losses, mtp_losses, grad_norms, held = [], [], None, None
        for s in range(FIRST_STEPS):
            tok, tgt = ref_pangu.batch_tokens(self.seed, s, self.batch, self.seq,
                                              self.c["vocab_size"], fault)
            r = ref.step(tok, tgt)
            losses.append(r["loss"])
            mtp_losses.append(r["mtp_loss"])
            if s == 0:   # the expert blocks' counts, as the program lists them
                grad_norms = r["grad_norms"]
                held = [n for n, (_, experts) in zip(ref.held, flops_pangu.blocks(self.c)) if experts]
        return {"losses": losses, "mtp_losses": mtp_losses, "grad_norms": grad_norms,
                "change_norms": ref.change_norms(), "held_by_block": held}
