"""Driver of the Keye-VL-2.0 cells: adapter fine-tuning over a frozen base on
one expert-parallel rank, ``LLMTrainer(cfg, args, mesh).fit(batches)`` with
``lora_rank`` set.

The adapter cells' driver (``sala.py``) with this configuration's model
(DeepSeek sparse attention: a frozen learned indexer chooses 2,048 keys a
query; softmax-routed expert layers of which one rank's experts are held),
its base and adapters from the seed and its float32 reference
(``ref_keye.py``), and its required work (``flops_keye.py``).  Beside the
three gaps it records the keys chosen and the assignments on held experts at
step 1: the program's summed over its layers, the reference's by layer.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from flax import traverse_util

import compare
import flops_keye
import ref_keye
import sala

FIRST_STEPS = sala.FIRST_STEPS


def transformer_config(c: dict, seq_len: int, remat_policy: str = "full", **overrides):
    """The program's ``TransformerConfig`` of a configuration file."""
    from fedml_tpu.models.transformer import TransformerConfig

    ref_keye.sizes(c)   # refuses what neither program nor reference has
    if c["n_routed_experts"] != c["num_experts"]:
        raise ValueError("n_routed_experts repeats num_experts, the experts held")
    sa = c["sa_config"]
    return TransformerConfig(**{**dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], max_seq_len=seq_len, rope_theta=float(c["rope_theta"]),
        norm_eps=c["rms_norm_eps"], dtype=jnp.bfloat16, remat=True, remat_policy=remat_policy,
        logits_dtype=jnp.bfloat16, mixer_types=("dsa",) * c["num_hidden_layers"],
        dsa_index_heads=sa["indexer_num_heads"], dsa_index_head_dim=sa["indexer_head_dim"],
        dsa_topk=sa["topk"], n_routed_experts=c["router_experts"], experts_held=c["num_experts"],
        first_expert=c["first_expert"], top_k=c["num_experts_per_tok"], moe_d_ff=c["moe_intermediate_size"],
        norm_topk_prob=c["norm_topk_prob"], router_scoring="softmax"), **overrides})


class Driver(sala.Driver):
    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int, devices):
        super().__init__(cell, config, traffic, seed, devices)
        flops_keye.check()  # the yardstick's counts, before anything is measured with them

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
        w = ref_keye.init_weights(c, self.seed, compare.flat(tr.param_shardings))
        if sorted(w) != sorted(compare.flat(tr.param_shardings)):
            raise ValueError("the reference's leaves are not the program's")
        tr.params = traverse_util.unflatten_dict(w, sep="/")
        # placed as the step returns them: an adapter tree that arrives under
        # another sharding type makes the step's second call compile again
        lora = sala.program_adapters(ref_keye.init_adapters(c, self.a, self.seed))
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
        grad_norms = {k: v / (1.0 - ref_keye.B1) for k, v in ref_keye.leaf_norms(mu).items()}
        h23 = self._fit(self._batches(count=FIRST_STEPS - 1))
        self.marks.append(("steps_2_3", time.perf_counter() - t0))
        change = ref_keye.change_norms(self.c, self.a, self.seed, self._adapters())
        # one more step so that the window's first finds the step program loaded again
        self._fit(self._batches(count=1))
        self.readings = {"losses": [h["loss"] for h in h1 + h23],
                         "grad_norms": grad_norms, "change_norms": change,
                         "held_in_step": h1[0]["moe_held"], "max_load_in_step": h1[0]["moe_max_load"],
                         "attended": [h1[0]["sparse_kept"], h1[0]["sparse_causal"]],
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
        t, layers = b * self.seq, c["num_hidden_layers"]
        # the rows the held experts REALLY saw in the window, spread evenly
        # over its steps and layers
        held = sum(h["moe_held"] for h in hist) / max(n * layers, 1)
        return {
            "work": float(t * n), "clock_s": clock, "attempted": n, "failed": 0,
            "pieces_s": [h["step_time_s"] for h in hist], "piece": "step",
            "flops_required": n * flops_keye.train_flops_per_step(c, self.job, b, self.seq),
            "roofline_work": {
                "matmul": [(flops_keye.step_matmuls(c, self.job, b, self.seq), n)],
                "moe": [(flops_keye.moe_products(c, t, held), n * layers)],
                "indexer": [(flops_keye.indexer_work(c, b, self.seq), n * layers)],
                "dsa": [([flops_keye.attention_work(c, b, self.seq)], n * layers)]},
            "losses": [h["loss"] for h in hist],
            # the routing each step saw: a step's rounds follow its busiest held expert
            "moe_held": [h["moe_held"] for h in hist],
            "moe_max_load": [h["moe_max_load"] for h in hist],
        }

    # -------------------------------------------------------------- check
    def reference(self, control=None, fault=None) -> dict:
        """The reference's readings, with its keys chosen and assignments on
        held experts by layer at step 1."""
        ref = ref_keye.ReferenceTrainer(self.c, self.a, self.seed, control=control)
        losses, grad_norms, out = [], None, {}
        for s in range(FIRST_STEPS):
            tok, tgt = ref_keye.batch_tokens(self.seed, s, self.batch, self.seq, self.c["vocab_size"], fault)
            r = ref.step(tok, tgt)
            losses.append(r["loss"])
            if s == 0:
                grad_norms = r["grad_norms"]
                out = {"chosen_by_layer": ref.kept, "held_by_block": ref.held}
        return {"losses": losses, "grad_norms": grad_norms, "change_norms": ref.change_norms(), **out}
