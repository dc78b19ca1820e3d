"""Driver of the LLM cells: ``LLMTrainer(cfg, args, mesh).fit(batches)``.

Set-up builds ONE trainer, gives it the benchmark's weights from the seed,
drives it through its first three steps by the window's own call and feed
(``fit`` over a generator of fresh host batches), and hands the same object
to the window.  The readings of those steps are what ``check`` holds against
the float32 reference once the window has closed and the trainer is freed.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp

import compare
import flops
import ref_llm

FIRST_STEPS = 3


class Driver:
    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int, devices):
        self.cell, self.c, self.t, self.seed, self.devices = cell, config, traffic, seed, devices
        self.a = dict(traffic["train_args"])
        self.batch, self.seq = traffic["batch_size"], traffic["seq_len"]
        self.next_step = 0
        self.readings: dict = {}
        # planted faults, for the tests under benchmark/ only
        self.fault = None

    # ------------------------------------------------------------- set-up
    def build(self) -> None:
        from fedml_tpu.llm.train import LLMTrainArgs, LLMTrainer
        from fedml_tpu.models.transformer import TransformerConfig
        from fedml_tpu.parallel import mesh as meshlib

        c = self.c
        cfg = TransformerConfig(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
            max_seq_len=self.seq, rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
            dtype=jnp.bfloat16, remat=True, remat_policy="dots", logits_dtype=jnp.bfloat16)
        if c["hidden_size"] != c["num_attention_heads"] * c["head_dim"]:
            raise ValueError("the program's model has head_dim = hidden/heads only")
        # the trainer bakes its seed into its init program (a compile per
        # seed) and its own draw is replaced below: it gets a fixed one
        args = LLMTrainArgs(batch_size=self.batch, seq_len=self.seq, seed=0, **self.a)
        mesh = meshlib.make_mesh((meshlib.AXIS_DATA,), devices=self.devices)
        t0 = time.perf_counter()
        self.trainer = tr = LLMTrainer(cfg, args, mesh=mesh)
        jax.block_until_ready(tr.opt_state)
        self.marks = [("LLMTrainer", time.perf_counter() - t0)]
        # the benchmark's weights from the seed, straight into the trainer's
        # own placement; the trainer's own draw is dropped first
        treedef = jax.tree_util.tree_structure(tr.params)
        names = list(compare.flat(tr.params))
        shardings = compare.flat(tr.param_shardings)
        for leaf in jax.tree_util.tree_leaves(tr.params):
            leaf.delete()
        w = ref_llm.init_weights(c, self.seed, shardings)
        tr.params = jax.tree_util.tree_unflatten(treedef, [w[n] for n in names])
        jax.block_until_ready(tr.params)
        self.marks.append(("weights_from_seed", time.perf_counter() - t0))
        self._step_program = tr._train_step

    def _batches(self, deadline=None, count=None):
        """The feed: a fresh host batch per step from (seed, step); stops at
        the deadline (checked before a step starts) or after ``count``."""
        made = 0
        while True:
            if deadline is not None and time.perf_counter() >= deadline:
                return
            if count is not None and made >= count:
                return
            with jax.profiler.TraceAnnotation("bench.batch_gen"):
                tok, tgt = ref_llm.batch_tokens(self.seed, self.next_step, self.batch,
                                                self.seq, self.c["vocab_size"])
                if self.fault == "half_batch":
                    tok, tgt = tok[: self.batch // 2], tgt[: self.batch // 2]
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

            def frozen(p, o, tok, tgt):
                cp = jax.tree_util.tree_map(jnp.copy, (p, o))
                _, _, m = real(*cp, tok, tgt)
                return p, o, m
            tr._train_step = frozen
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
        grad_norms = {k: v / (1.0 - ref_llm.B1) for k, v in ref_llm.leaf_norms(mu).items()}
        self.marks.append(("grad_norms", time.perf_counter() - t0))
        h23 = self._fit(self._batches(count=FIRST_STEPS - 1))
        self.marks.append(("steps_2_3", time.perf_counter() - t0))
        change = ref_llm.change_norms(self.c, self.seed, compare.flat(tr.params))
        self.marks.append(("change_norms", time.perf_counter() - t0))
        # one more step so that the window's first finds the step program
        # loaded again (after another program a step takes 32 ms longer)
        self._fit(self._batches(count=1))
        self.readings = {"losses": [h["loss"] for h in h1 + h23],
                         "grad_norms": grad_norms, "change_norms": change}
        steady = min(h["step_time_s"] for h in h23)
        return {"first_call_s": first_s, "steady_s": steady}

    # ------------------------------------------------------------- window
    def on_kernel(self) -> float:
        """Share of the step program's blockwise-attention call sites that
        the fused flash kernel took (``LLMTrainer.attention_sites``, counted
        while the step was traced); 0 where none was counted (the ring, or a
        model without softmax attention)."""
        sites = getattr(self.trainer, "attention_sites", None) or {}
        return sites.get("kernel", 0) / max(sum(sites.values()), 1)

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            hist = self._fit(self._batches(deadline=t0 + seconds))
            clock = time.perf_counter() - t0
        tokens = self.batch * self.seq * len(hist)
        c, n = self.c, len(hist)
        # attention's products are the matmul fusions' where XLA runs them,
        # the kernel's own where the flash kernel does
        layers, kernel = c["num_hidden_layers"], self.on_kernel()
        return {
            "work": float(tokens), "clock_s": clock, "attempted": n, "failed": 0,
            "pieces_s": [h["step_time_s"] for h in hist], "piece": "step",
            "flops_required": tokens * flops.transformer_train_flops_per_token(c, self.seq),
            "roofline_work": {
                "matmul": [(flops.transformer_step_matmuls(c, self.batch, self.seq, attention=False), n),
                           (flops.attention_products(c, self.batch, self.seq), n * layers * (1 - kernel))],
                "flash": [([flops.attention_work(c, self.batch, self.seq)], n * layers * kernel)]},
            "losses": [h["loss"] for h in hist],
        }

    def program_memory(self) -> dict:
        """Bytes of the compiled step (arguments, outputs, temporaries) from
        the compiler's own analysis: the persistent cache answers, nothing
        recompiles on the device."""
        tr = self.trainer
        tok = jax.ShapeDtypeStruct((self.batch, self.seq), jnp.int32, sharding=tr.data_sharding)
        ma = self._step_program.lower(tr.params, tr.opt_state, tok, tok).compile().memory_analysis()
        out = {"argument": ma.argument_size_in_bytes, "output": ma.output_size_in_bytes,
               "alias": ma.alias_size_in_bytes, "temp": ma.temp_size_in_bytes}
        # what one device holds while the step runs: its arguments (state
        # and batch; the donated state's outputs alias them) and temporaries
        out["resident_and_temp"] = (out["argument"] + out["output"] - out["alias"] + out["temp"])
        return out

    def free(self) -> None:
        for leaf in jax.tree_util.tree_leaves((self.trainer.params, self.trainer.opt_state)):
            leaf.delete()
        self.trainer = None
        gc.collect()

    # -------------------------------------------------------------- check
    def reference(self, control=None, fault=None) -> dict:
        ref = ref_llm.ReferenceTrainer(self.c, self.a, self.seed, control=control, fault=fault)
        losses, grad_norms = [], None
        for s in range(FIRST_STEPS):
            tok, tgt = ref_llm.batch_tokens(self.seed, s, self.batch, self.seq,
                                            self.c["vocab_size"])
            r = ref.step(tok, tgt, last=(s == FIRST_STEPS - 1))
            losses.append(r["loss"])
            if s == 0:
                grad_norms = r["grad_norms"]
        return {"losses": losses, "grad_norms": grad_norms, "change_norms": ref.change_norms()}

    def gaps(self, prog: dict, ref: dict) -> dict:
        return compare.training_gaps(prog, ref)

    def check(self, limits: dict) -> tuple[bool, dict, dict]:
        self.reference_readings = self.reference()
        gaps = self.gaps(self.readings, self.reference_readings)
        ok, compared = compare.judge(gaps, limits)
        return ok, compared, gaps
