"""Driver of the FedAvg cells: ``fedml_tpu.init(cfg)`` ->
``FedMLRunner(cfg, dataset).runner`` -> ``run_rounds(k)`` and ``evaluate()``,
the two calls ``MeshSimulator.run`` makes between host boundaries.

Set-up builds ONE simulator on the benchmark's data and weights from the
seed, drives it through its first chunks by the window's own calls, and hands
the same object to the window.  ``check`` holds the readings of those chunks
against the float32 reference once the window has closed and the simulator's
arrays are freed.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import compare
import flops
import ref_fedavg


class Driver:
    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int, devices):
        self.cell, self.c, self.t, self.seed, self.devices = cell, config, traffic, seed, devices
        self.chunk = traffic["rounds_per_chunk"]
        self.first_chunks = traffic["first_chunks"]          # driven before the window
        self.compared_chunks = traffic["compared_chunks"]    # followed by the reference
        self.readings: dict = {}
        self.fault = None  # planted faults, for the tests under benchmark/ only
        self._made: dict | None = None

    @property
    def data(self) -> dict:
        if self._made is None:
            self._made = ref_fedavg.make_data(self.t, self.seed)
        return self._made

    # ------------------------------------------------------------- set-up
    def build(self) -> None:
        import fedml_tpu
        from fedml_tpu.arguments import Config
        from fedml_tpu.data.dataset import FederatedDataset
        from fedml_tpu.runner import FedMLRunner

        t = self.t
        t0 = time.perf_counter()
        d = self.data
        self.marks = [("make_data", time.perf_counter() - t0)]
        batch = t["batch_size"] // 2 if self.fault == "half_batch" else t["batch_size"]
        cfg = Config(
            dataset="cifar10", model="resnet20", federated_optimizer="FedAvg",
            client_num_in_total=t["clients_total"], client_num_per_round=t["clients_per_round"],
            comm_round=10 ** 6, epochs=t["epochs"], batch_size=batch,
            client_optimizer="sgd", learning_rate=t["learning_rate"],
            partition_method=t["partition_method"], partition_alpha=t.get("partition_alpha", 0.5),
            frequency_of_the_test=self.chunk, compute_dtype="bfloat16", step_mode="match",
            metrics_jsonl_path="", random_seed=ref_fedavg.round_seed(t, self.seed),
            mesh_shape=f"clients:{len(self.devices)}")
        fedml_tpu.init(cfg)
        # init() puts the cache where core/cache.py says and resets its
        # threshold; the benchmark keeps every program, however quick
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        dataset = FederatedDataset(
            train_x=d["train_x"], train_y=d["train_y"], test_x=d["test_x"], test_y=d["test_y"],
            client_idx=d["clients"], class_num=d["classes"], name="cifar10-standin")
        model = None
        if self.c["blocks_per_stage"] != 3:  # the rehearsal's depth cut; the chip runs the hub's resnet20
            from fedml_tpu.models import resnet

            model = resnet.CifarResNet(num_blocks=self.c["blocks_per_stage"], dtype=jnp.bfloat16)
        self.sim = sim = FedMLRunner(cfg, dataset=dataset, model=model).runner
        self.marks.append(("init_and_runner", time.perf_counter() - t0))
        if sim.mesh.devices.size != len(self.devices):
            raise RuntimeError(f"mesh {dict(sim.mesh.shape)} is not the cell's {len(self.devices)} chips")
        # the benchmark's weights from the seed, placed as the simulator's own
        # kept on the host: the chunk donates what it is given
        self.w0 = {k: np.asarray(v) for k, v in ref_fedavg.init_weights(self.c, self.seed).items()}
        names = list(compare.flat(sim.global_vars))
        if sorted(names) != sorted(self.w0):
            raise RuntimeError("the program's variable tree is not the reference's: "
                               f"{sorted(set(names) ^ set(self.w0))[:6]}")
        leaves, treedef = jax.tree_util.tree_flatten(sim.global_vars)
        sim.global_vars = jax.tree_util.tree_unflatten(treedef, [
            jax.device_put(self.w0[n].astype(old.dtype), old.sharding)
            for n, old in zip(names, leaves)])
        self.counts = np.array([len(c) for c in d["clients"]], np.int64)

    def _chunk(self) -> tuple[list[dict], dict, float, float]:
        sim = self.sim
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.run_rounds"):
            if self.fault == "state_unchanged":
                keep = jax.tree_util.tree_map(jnp.copy, sim.global_vars)
                rounds = sim.run_rounds(self.chunk)
                sim.global_vars = keep
            else:
                rounds = sim.run_rounds(self.chunk)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.evaluate"):
            ev = sim.evaluate()
        return rounds, ev, t1 - t0, time.perf_counter() - t1

    def _changes(self) -> dict:
        return ref_fedavg.change_norms(
            {k: v.astype(jnp.float32) for k, v in compare.flat(self.sim.global_vars).items()}, self.w0)

    def first_steps(self) -> dict:
        losses, evals, times, first_change, last_change = [], [], [], None, None
        for i in range(self.first_chunks):
            rounds, ev, run_s, eval_s = self._chunk()
            times.append(run_s + eval_s)
            if i < self.compared_chunks:
                losses += [r["train_loss"] for r in rounds]
                evals.append(ev)
            if i == 0:
                first_change = self._changes()
            if i == self.compared_chunks - 1:
                last_change = first_change if i == 0 else self._changes()
        self.readings = {"losses": losses, "grad_norms": first_change,
                         "change_norms": last_change, "evals": evals}
        return {"first_call_s": times[0], "steady_s": min(times[1:])}

    # ------------------------------------------------------------- window
    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        start_round = self.sim.round_idx
        run_s, eval_s = [], []
        with jax.profiler.TraceAnnotation("bench.window"):
            while time.perf_counter() < t0 + seconds:
                _, _, a, b = self._chunk()
                run_s.append(a)
                eval_s.append(b)
            clock = time.perf_counter() - t0
        n_rounds = self.sim.round_idx - start_round
        samples, steps = self._real_work(start_round, n_rounds)
        t = self.t
        per_sample = flops.resnet20_train_flops_per_sample()
        work = {"samples_per_s": float(samples), "rounds_per_s": float(n_rounds)}[t["rate_metric"]]
        eval_convs = flops.resnet20_step_convs(t["test_samples"])[::3]  # the forward products
        return {
            "work": work, "clock_s": clock, "attempted": len(run_s), "failed": 0,
            "pieces_s": run_s, "piece": f"chunk of {self.chunk} round(s)", "eval_s": eval_s,
            "round_s": [(a + b) / self.chunk for a, b in zip(run_s, eval_s)],
            "rounds": n_rounds, "samples": samples, "local_steps": steps,
            "flops_required": samples * per_sample,
            "roofline_work": {"conv": [(flops.resnet20_step_convs(t["batch_size"]), steps),
                                       (eval_convs, len(eval_s))]},
        }

    def _real_work(self, start_round: int, n_rounds: int) -> tuple[int, int]:
        """Real samples trained and real local steps taken in the window's
        rounds: the sampled clients' own counts (never lanes x capacity),
        from the sampling rule written out in the reference."""
        t = self.t
        n, m = t["clients_total"], min(t["clients_per_round"], t["clients_total"])
        root = jax.random.PRNGKey(ref_fedavg.round_seed(t, self.seed))
        if n <= m:
            per_round = np.tile(np.arange(n), (n_rounds, 1))
        else:
            perm = jax.jit(jax.vmap(lambda r: jax.random.permutation(jax.random.fold_in(root, r), n)[:m]))
            per_round = np.asarray(perm(jnp.arange(start_round, start_round + n_rounds)))
        cnt = self.counts[per_round]
        return (int(cnt.sum()) * t["epochs"],
                int((-(-cnt // t["batch_size"])).sum()) * t["epochs"])

    def program_memory(self) -> dict:
        """Bytes of the compiled chunk (arguments, outputs, temporaries) by
        the compiler's own analysis of the program the window ran."""
        ma = self.sim._multi_round_fns[self.chunk].memory_analysis()
        out = {"argument": ma.argument_size_in_bytes, "output": ma.output_size_in_bytes,
               "alias": ma.alias_size_in_bytes, "temp": ma.temp_size_in_bytes}
        out["resident_and_temp"] = out["argument"] + out["output"] - out["alias"] + out["temp"]
        return out

    def free(self) -> None:
        sim = self.sim
        for leaf in jax.tree_util.tree_leaves((sim.global_vars, sim._data, sim._test, sim.counts)):
            leaf.delete()
        self.sim = None
        gc.collect()

    # -------------------------------------------------------------- check
    def reference(self, control=None, fault=None) -> dict:
        ref = ref_fedavg.ReferenceFedAvg(self.c, self.t, self.seed, self.data,
                                         control=control, fault=fault)
        losses, evals, first_change = [], [], None
        for i in range(self.compared_chunks):
            losses += [ref.round() for _ in range(self.chunk)]
            evals.append(ref.evaluate())
            if i == 0:
                first_change = ref.change_norms()
        return {"losses": losses, "grad_norms": first_change,
                "change_norms": ref.change_norms(), "evals": evals}

    def gaps(self, prog: dict, ref: dict) -> dict:
        gaps = compare.training_gaps(prog, ref)
        # the mean over the compared rounds of the loss's gap: one round's gap
        # swings tenfold from seed to seed (rounding amplified by the steps
        # that follow), a lower precision shows as a bias in every round
        per_round = [abs(p - r) / abs(r) for p, r in zip(prog["losses"], ref["losses"])]
        gaps["mean_loss_gap"] = sum(per_round) / len(per_round)
        gaps["eval_loss_gap"] = max(abs(p["test_loss"] - r["test_loss"]) / abs(r["test_loss"])
                                    for p, r in zip(prog["evals"], ref["evals"]))
        gaps["eval_acc_gap"] = max(abs(p["test_acc"] - r["test_acc"])
                                   for p, r in zip(prog["evals"], ref["evals"]))
        return gaps

    def check(self, limits: dict) -> tuple[bool, dict, dict]:
        self.reference_readings = self.reference()
        gaps = self.gaps(self.readings, self.reference_readings)
        ok, compared = compare.judge(gaps, limits)
        return ok, compared, gaps
