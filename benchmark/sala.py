"""Driver of the MiniCPM-SALA cells: adapter fine-tuning over a frozen base,
``LLMTrainer(cfg, args, mesh).fit(batches)`` with ``lora_rank`` set.

The LLM cells' driver (``llm.py``) with what adapter mode changes: the
configuration's mixers, the frozen bfloat16 base and the float32 adapters from
the seed (``ref_sala.py``), readings taken on the adapters (the first gradient
from Adam's first moment, the change after three steps), the float32 reference
of ``ref_sala.py`` and the required work of ``flops_sala.py``.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
from flax import traverse_util

import compare
import flops_sala
import llm
import ref_sala

FIRST_STEPS = llm.FIRST_STEPS
# what the program's two mixers do, as the published model has it: a
# configuration that states otherwise has no program and no reference
MIXERS_DO = {"qk_norm": True, "attn_use_rope": False, "attn_use_output_gate": True,
             "lightning_use_rope": True, "use_output_gate": True, "use_output_norm": True}


def transformer_config(c: dict, seq_len: int, remat_policy: str = "dots", **overrides):
    """The program's ``TransformerConfig`` of a configuration file."""
    from fedml_tpu.models.transformer import TransformerConfig

    z = c["sparse_config"]
    if c["hidden_size"] != c["num_attention_heads"] * c["head_dim"] or c["lightning_nkv"] != c["lightning_nh"]:
        raise ValueError("the program's mixers have heads x head_dim = hidden and lightning_nkv = lightning_nh")
    if any(c[k] != v for k, v in MIXERS_DO.items()):
        raise ValueError(f"the program's mixers are MiniCPM-SALA's: {MIXERS_DO}")
    return TransformerConfig(**{**dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], d_ff=c["intermediate_size"], max_seq_len=seq_len,
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"], dtype=jnp.bfloat16,
        remat=True, remat_policy=remat_policy, logits_dtype=jnp.bfloat16,
        mixer_types=tuple(c["mixer_types"][: c["num_hidden_layers"]]),
        sparse_kernel_size=z["kernel_size"], sparse_kernel_stride=z["kernel_stride"],
        sparse_block_size=z["block_size"], sparse_topk=z["topk"],
        sparse_init_blocks=z["init_blocks"], sparse_window_size=z["window_size"],
        sparse_dense_len=z["dense_len"], lightning_heads=c["lightning_nh"],
        lightning_head_dim=c["lightning_head_dim"],
        scale_emb=float(c["scale_emb"]), scale_depth=c["scale_depth"],
        mup_depth=c["mup_denominator"], dim_model_base=c["dim_model_base"]), **overrides})


def program_adapters(flat: dict) -> dict:
    """The reference's ``{"<kernel>/a": .., "<kernel>/b": ..}`` as the
    program's ``{"<kernel>": {"a": .., "b": ..}}``."""
    out: dict = {}
    for path, leaf in flat.items():
        kernel, _, ab = path.rpartition("/")
        out.setdefault(kernel, {})[ab] = leaf
    return out


class Driver(llm.Driver):
    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int, devices):
        super().__init__(cell, config, traffic, seed, devices)
        flops_sala.check()  # the yardstick's counts, before anything is measured with them
        # the job's adapters, apart from the optimizer's sizes
        self.job = {k: self.a[k] for k in ("lora_rank", "lora_alpha", "lora_targets")}

    # ------------------------------------------------------------- set-up
    def build(self) -> None:
        from fedml_tpu.llm.train import LLMTrainArgs, LLMTrainer
        from fedml_tpu.parallel import mesh as meshlib

        c, t = self.c, self.t
        cfg = transformer_config(c, self.seq, t.get("remat_policy", "dots"), **t.get("program", {}))
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
        w = ref_sala.init_weights(c, self.seed, compare.flat(tr.param_shardings))
        if sorted(w) != sorted(compare.flat(tr.param_shardings)):
            raise ValueError("the reference's leaves are not the program's")
        tr.params = traverse_util.unflatten_dict(w, sep="/")
        tr.lora = program_adapters(ref_sala.init_adapters(c, self.a, self.seed))
        jax.block_until_ready((tr.params, tr.lora))
        self.marks.append(("weights_from_seed", time.perf_counter() - t0))
        self._step_program = tr._train_step

    def _batches(self, deadline=None, count=None):
        """``llm.Driver``'s feed from ``ref_sala.batch_tokens`` (which knows
        what half of a one-row batch is)."""
        made = 0
        while True:
            if deadline is not None and time.perf_counter() >= deadline:
                return
            if count is not None and made >= count:
                return
            with jax.profiler.TraceAnnotation("bench.batch_gen"):
                tok, tgt = ref_sala.batch_tokens(self.seed, self.next_step, self.batch, self.seq,
                                                 self.c["vocab_size"], self.fault)
            self.next_step += 1
            made += 1
            span = jax.profiler.TraceAnnotation("bench.llm_step")
            span.__enter__()
            try:
                yield tok, tgt
            finally:
                span.__exit__(None, None, None)

    def _fit(self, batches) -> list[dict]:
        tr = self.trainer
        if self.fault == "state_unchanged":
            real = self._step_program

            def frozen(lora, opt_state, base, tok, tgt):
                _, _, m = real(*jax.tree_util.tree_map(jnp.copy, (lora, opt_state)), base, tok, tgt)
                return lora, opt_state, m
            tr._train_step = frozen
        return tr.fit(batches, steps=10 ** 9)

    def _adapters(self) -> dict:
        return {f"{k}/{ab}": v for k, d in self.trainer.lora.items() for ab, v in d.items()}

    def first_steps(self) -> dict:
        """Steps 1..3 through ``fit``; step 1 compiles (or loads).  Records
        the program's readings for ``check``."""
        tr = self.trainer
        t0 = time.perf_counter()
        h1 = self._fit(self._batches(count=1))
        first_s = time.perf_counter() - t0
        self.marks.append(("first_step", first_s))
        mu = {k.split("/mu/", 1)[1]: v for k, v in compare.flat(tr.opt_state).items() if "/mu/" in k}
        grad_norms = {k: v / (1.0 - ref_sala.B1) for k, v in ref_sala.leaf_norms(mu).items()}
        h23 = self._fit(self._batches(count=FIRST_STEPS - 1))
        self.marks.append(("steps_2_3", time.perf_counter() - t0))
        change = ref_sala.change_norms(self.c, self.a, self.seed, self._adapters())
        # one more step so that the window's first finds the step program loaded again
        self._fit(self._batches(count=1))
        self.readings = {"losses": [h["loss"] for h in h1 + h23],
                         "grad_norms": grad_norms, "change_norms": change,
                         "attended": [h1[0].get("sparse_kept"), h1[0].get("sparse_causal")]}
        steady = min(h["step_time_s"] for h in h23)
        return {"first_call_s": first_s, "steady_s": steady}

    # ------------------------------------------------------------- window
    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            hist = self._fit(self._batches(deadline=t0 + seconds))
            clock = time.perf_counter() - t0
        tokens = self.batch * self.seq * len(hist)
        c, n = self.c, len(hist)
        mixers = lambda kind: [(flops_sala.mixers_work(c, kind, self.batch, self.seq), n)]
        return {
            "work": float(tokens), "clock_s": clock, "attempted": n, "failed": 0,
            "pieces_s": [h["step_time_s"] for h in hist], "piece": "step",
            "flops_required": n * flops_sala.train_flops_per_step(c, self.job, self.batch, self.seq),
            "roofline_work": {"matmul": [(flops_sala.step_matmuls(c, self.job, self.batch, self.seq), n)],
                              "lightning": mixers("lightning-attn"), "sparse": mixers("minicpm4")},
            "losses": [h["loss"] for h in hist],
        }

    def program_memory(self) -> dict:
        tr = self.trainer
        tok = jax.ShapeDtypeStruct((self.batch, self.seq), jnp.int32, sharding=tr.data_sharding)
        ma = self._step_program.lower(tr.lora, tr.opt_state, tr.params, tok, tok).compile().memory_analysis()
        out = {"argument": ma.argument_size_in_bytes, "output": ma.output_size_in_bytes,
               "alias": ma.alias_size_in_bytes, "temp": ma.temp_size_in_bytes}
        out["resident_and_temp"] = (out["argument"] + out["output"] - out["alias"] + out["temp"])
        return out

    def free(self) -> None:
        tr = self.trainer
        for leaf in jax.tree_util.tree_leaves((tr.params, tr.lora, tr.opt_state)):
            leaf.delete()
        self.trainer = None
        gc.collect()

    # -------------------------------------------------------------- check
    def reference(self, control=None, fault=None) -> dict:
        ref = ref_sala.ReferenceTrainer(self.c, self.a, self.seed, control=control)
        losses, grad_norms = [], None
        for s in range(FIRST_STEPS):
            tok, tgt = ref_sala.batch_tokens(self.seed, s, self.batch, self.seq,
                                             self.c["vocab_size"], fault)
            r = ref.step(tok, tgt)
            losses.append(r["loss"])
            if s == 0:
                grad_norms = r["grad_norms"]
        return {"losses": losses, "grad_norms": grad_norms, "change_norms": ref.change_norms(),
                "attended": list(ref.attended)}
