"""Driver of the Granite 4.0-H cells: adapter fine-tuning over a frozen base on
PACKED documents, ``LLMTrainer(cfg, args, mesh).fit(batches)`` with
``lora_rank`` set and batches of three arrays ``(tokens, targets, segments)``.

The adapter cells' driver (``sala.py``) with this configuration's model
(Mamba-2 layers beside grouped-query attention without positions, the Granite
multipliers, a tied head), its base and adapters from the seed and its float32
reference (``ref_granite.py``, which runs every document alone), its required
work (``flops_granite.py``), and the packed feed: each step draws the
traffic's documents in a fresh order and with fresh ids from (seed, step) and
packs them through ``fedml_tpu/llm/packing.pack`` inside ``llm.next_batch``.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from flax import traverse_util

import compare
import flops_granite
import ref_granite
import sala

FIRST_STEPS = sala.FIRST_STEPS


def transformer_config(c: dict, seq_len: int, remat_policy: str = "full", **overrides):
    """The program's ``TransformerConfig`` of a configuration file."""
    from fedml_tpu.models.transformer import TransformerConfig

    ref_granite.sizes(c)   # refuses what neither program nor reference has
    return TransformerConfig(**{**dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_ff=c["shared_intermediate_size"], max_seq_len=seq_len, rope_theta=float(c["rope_theta"]),
        norm_eps=c["rms_norm_eps"], dtype=jnp.bfloat16, remat=True, remat_policy=remat_policy,
        logits_dtype=jnp.bfloat16, mixer_types=tuple(c["layer_types"][: c["num_hidden_layers"]]),
        mamba_heads=c["mamba_n_heads"], mamba_head_dim=c["mamba_d_head"],
        mamba_d_state=c["mamba_d_state"], mamba_groups=c["mamba_n_groups"],
        mamba_d_conv=c["mamba_d_conv"], mamba_chunk=c["mamba_chunk_size"],
        attn_rope=False, attn_scale=c["attention_multiplier"], tie_embeddings=True,
        scale_emb=float(c["embedding_multiplier"]), scale_depth=c["residual_multiplier"], mup_depth=1,
        dim_model_base=int(c["hidden_size"] / c["logits_scaling"])), **overrides})


class Driver(sala.Driver):
    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int, devices):
        super().__init__(cell, config, traffic, seed, devices)
        self.lengths = list(traffic["doc_lengths"])
        if sum(self.lengths) != self.seq:
            raise ValueError("the traffic's documents fill a row exactly")

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
        w = ref_granite.init_weights(c, self.seed, compare.flat(tr.param_shardings))
        if sorted(w) != sorted(compare.flat(tr.param_shardings)):
            raise ValueError("the reference's leaves are not the program's")
        tr.params = traverse_util.unflatten_dict(w, sep="/")
        # placed as the step returns them: an adapter tree that arrives under
        # another sharding type makes the step's second call compile again
        lora = sala.program_adapters(ref_granite.init_adapters(c, self.a, self.seed))
        tr.lora = jax.device_put(lora, sharding.named_shardings(lora, mesh))
        jax.block_until_ready((tr.params, tr.lora))
        self.marks.append(("weights_from_seed", time.perf_counter() - t0))
        self._step_program = tr._train_step_packed

    def _documents(self, step: int, fault=None):
        return ref_granite.batch_documents(self.seed, step, self.batch, self.lengths,
                                           self.c["vocab_size"], fault)

    def _batches(self, deadline=None, count=None):
        """``llm.Driver``'s feed, packed: the step's documents from (seed,
        step), laid into rows by the program's own ``pack``."""
        import numpy as np
        from fedml_tpu.llm.packing import pack

        made = 0
        while True:
            if deadline is not None and time.perf_counter() >= deadline:
                return
            if count is not None and made >= count:
                return
            with jax.profiler.TraceAnnotation("bench.batch_gen"):
                rows = [pack(docs, self.seq) for docs in self._documents(self.next_step, self.fault)]
                batch = tuple(np.concatenate(parts) for parts in zip(*rows))
            self.next_step += 1
            made += 1
            span = jax.profiler.TraceAnnotation("bench.llm_step")
            span.__enter__()
            try:
                yield batch
            finally:
                span.__exit__(None, None, None)

    def _fit(self, batches) -> list[dict]:
        tr = self.trainer
        if self.fault == "state_unchanged":
            real = self._step_program

            def frozen(lora, opt_state, base, *batch):
                _, _, m = real(*jax.tree_util.tree_map(jnp.copy, (lora, opt_state)), base, *batch)
                return lora, opt_state, m
            tr._train_step_packed = frozen
        return tr.fit(batches, steps=10 ** 9)

    def first_steps(self) -> dict:
        """Steps 1..3 through ``fit``; step 1 compiles (or loads).  Records
        the program's readings for ``check``."""
        tr = self.trainer
        t0 = time.perf_counter()
        h1 = self._fit(self._batches(count=1))
        first_s = time.perf_counter() - t0
        self.marks.append(("first_step", first_s))
        mu = {k.split("/mu/", 1)[1]: v for k, v in compare.flat(tr.opt_state).items() if "/mu/" in k}
        grad_norms = {k: v / (1.0 - ref_granite.B1) for k, v in ref_granite.leaf_norms(mu).items()}
        h23 = self._fit(self._batches(count=FIRST_STEPS - 1))
        self.marks.append(("steps_2_3", time.perf_counter() - t0))
        change = ref_granite.change_norms(self.c, self.a, self.seed, self._adapters())
        # one more step so that the window's first finds the step program loaded again
        self._fit(self._batches(count=1))
        self.readings = {"losses": [h["loss"] for h in h1 + h23],
                         "grad_norms": grad_norms, "change_norms": change,
                         "packed": {k: h1[0][k] for k in ("docs", "loss_tokens", "doc_pairs", "causal_pairs")},
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
        kinds = flops_granite.kinds(c)
        mamba, attention = kinds.count("mamba"), kinds.count("attention")
        return {
            "work": float(b * self.seq * n), "clock_s": clock, "attempted": n, "failed": 0,
            "pieces_s": [h["step_time_s"] for h in hist], "piece": "step",
            "flops_required": n * flops_granite.train_flops_per_step(c, self.job, b, self.lengths),
            "roofline_work": {
                "matmul": [(flops_granite.step_matmuls(c, self.job, b, self.seq), n)],
                "flash": [([flops_granite.attention_work(c, b, self.lengths)],
                           n * attention * self.on_kernel())],
                "ssd": [([flops_granite.scan_work(c, b, self.seq)], n * mamba)],
                "conv": [([flops_granite.conv_work(c, b, self.seq)], n * mamba)],
                "attention": [(flops_granite.attention_module_work(c, b, self.lengths), n * attention)]},
            "losses": [h["loss"] for h in hist],
            "loss_tokens": [h["loss_tokens"] for h in hist],
            "doc_pairs": [h["doc_pairs"] for h in hist],
        }

    def program_memory(self) -> dict:
        tr = self.trainer
        tok = jax.ShapeDtypeStruct((self.batch, self.seq), jnp.int32, sharding=tr.data_sharding)
        ma = self._step_program.lower(tr.lora, tr.opt_state, tr.params, tok, tok, tok).compile().memory_analysis()
        out = {"argument": ma.argument_size_in_bytes, "output": ma.output_size_in_bytes,
               "alias": ma.alias_size_in_bytes, "temp": ma.temp_size_in_bytes}
        out["resident_and_temp"] = (out["argument"] + out["output"] - out["alias"] + out["temp"])
        return out

    # -------------------------------------------------------------- check
    def reference(self, control=None, fault=None) -> dict:
        """The reference's readings; ``fault`` ``half_batch`` changes the
        documents, ``no_reset`` how the reference runs them."""
        ref = ref_granite.ReferenceTrainer(self.c, self.a, self.seed, control=control)
        losses, grad_norms, counted = [], None, None
        for s in range(FIRST_STEPS):
            rows = self._documents(s, fault if fault == "half_batch" else None)
            r = ref.step(rows, fault if fault == "no_reset" else None)
            losses.append(r["loss"])
            if s == 0:
                grad_norms, counted = r["grad_norms"], r["loss_tokens"]
        return {"losses": losses, "grad_norms": grad_norms, "change_norms": ref.change_norms(),
                "loss_tokens": counted}
