#!/usr/bin/env python3
"""chip_smoke.py — the one command that proves the main path runs on the TPU.

    python3 chip_smoke.py                # on a machine with a TPU; exit 0 = up
    python3 chip_smoke.py --dry-run-cpu  # tiny shapes, interpreted kernels

Drives, through the entry points a user calls and at the flagship widths:

  Leg A  the FedAvg ResNet-20 round (``fedml_tpu.init`` -> ``FedMLRunner.run``
         -> scanned chunks with a donated carry + ``evaluate``);
  Leg C  every Pallas kernel compiled by Mosaic (``interpret=False``) against
         the jnp reference beside it;
  Leg D  latent attention (``MLAttention``) at a small shape that tiles: the
         fused flash kernel must be the path taken, and agree with the
         blockwise ``lax`` pass in the output and the input's gradient;
  Leg E  the Mamba-2 mixer (``Mamba``) on packed rows at a small shape that
         tiles: the fused selective-scan kernel pair must be the path taken,
         and agree with the ``lax.scan`` in the output and the input's gradient;
  Leg B  the 542M-parameter LLM train step (``LLMTrainer.fit``), on every mesh
         the visible devices allow.

It refuses to start unless ``jax.devices()[0].platform == "tpu"``: no CPU
continuation, because every kernel would then run interpreted and pass.  Any
failed leg makes the exit code non-zero.  The last stdout line is one JSON
object naming the device as JAX reports it.  ``--dry-run-cpu`` exists to
debug the control flow in a sandbox; it labels everything ``dry_run`` and is
never reached implicitly.

One process, one chip (or one host's chips): the legs run in sequence and
drop their references, no child process is started.  Wall seconds are printed
per leg (Legs A and B split into compile and steady), as set-up facts — not
as metrics.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def memory(jax) -> list[dict]:
    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append({"in_use": stats.get("bytes_in_use"),
                    "peak": stats.get("peak_bytes_in_use")})
    return out


def fmt_memory(mem: list[dict]) -> str:
    """``in-use/peak MiB`` per device (peak is the process's so far)."""
    return ", ".join(
        "n/a" if m["in_use"] is None
        else f"{m['in_use'] / 2**20:.0f}/{m['peak'] / 2**20:.0f} MiB" for m in mem)


def check_spread(jax, tree, what: str) -> None:
    """Every leaf lives on every local device, and every device holds bytes:
    a mesh that quietly put everything on chip 0 must not pass."""
    n = jax.device_count()
    for leaf in jax.tree_util.tree_leaves(tree):
        check(len(leaf.sharding.device_set) == n,
              f"{what}: a leaf of shape {leaf.shape} sits on "
              f"{len(leaf.sharding.device_set)} of {n} devices")
    for d, m in zip(jax.devices(), memory(jax)):
        check(m["in_use"] is None or m["in_use"] > 0,
              f"{what}: {d} reports no bytes in use")


# ---------------------------------------------------------------------------
# Leg A — flagship FedAvg round (bench.py's fedavg shape)
# ---------------------------------------------------------------------------

def fl_recipe(dry: bool, rounds: int, eval_every: int):
    from fedml_tpu.arguments import Config

    n_clients, per_round, per_client, batch, n_test = (
        (4, 2, 16, 8, 32) if dry else (128, 64, 512, 128, 1024))
    return Config(
        dataset="cifar10", model="resnet20",
        client_num_in_total=n_clients, client_num_per_round=per_round,
        comm_round=rounds, epochs=1, batch_size=batch, learning_rate=0.03,
        partition_method="homo",
        synthetic_train_size=n_clients * per_client, synthetic_test_size=n_test,
        frequency_of_the_test=eval_every, compute_dtype="bfloat16",
        step_mode="match", metrics_jsonl_path="", random_seed=0,
    )


def run_fl(jax, dry: bool, rounds: int, eval_every: int) -> dict:
    """``init`` -> ``FedMLRunner`` -> ``run`` on the recipe; returns history,
    the compile/steady split and a host copy of the final flat model.  The
    default mesh must span every device."""
    import math

    import fedml_tpu
    from fedml_tpu.core import pytree as pt
    from fedml_tpu.runner import FedMLRunner
    from fedml_tpu.sim import engine

    cfg = fl_recipe(dry, rounds, eval_every)
    fedml_tpu.init(cfg)
    model = None
    if dry:  # depth cut for the sandbox; the chip runs ResNet-20 from the hub
        import jax.numpy as jnp

        from fedml_tpu.models import resnet

        model = resnet.CifarResNet(num_blocks=1, dtype=jnp.bfloat16)
    compile0 = engine.CHUNK_COMPILE_TIME.sum()
    runner = FedMLRunner(cfg, model=model)
    sim = runner.runner
    check(sim.mesh.devices.size == jax.device_count(),
          f"mesh {dict(sim.mesh.shape)} does not span "
          f"{jax.device_count()} devices")
    check_spread(jax, (sim._data, sim.client_states, sim.global_vars,
                       sim._test), "FL placement")
    history = runner.run()
    check(len(history) == rounds, f"{len(history)} rounds of {rounds} ran")
    for h in history:
        for k, v in h.items():
            check(math.isfinite(float(v)), f"round {h['round']}: {k}={v}")
    check(history[-1]["train_loss"] < history[0]["train_loss"],
          f"train_loss did not fall: {history[0]['train_loss']} -> "
          f"{history[-1]['train_loss']}")
    if eval_every:
        check("test_acc" in history[eval_every - 1] and "test_acc" in history[-1],
              "evaluate() did not run at the eval boundary")
    flat, _ = pt.tree_flatten_to_vector(sim.global_vars)
    return {
        "history": history,
        "compile_s": engine.CHUNK_COMPILE_TIME.sum() - compile0,
        # the last chunk reuses the first chunk's program: no compile in it
        "steady_s": history[-1]["chunk_time_s"],
        "chunk_rounds": history[-1]["chunk_rounds"],
        "flat_model": jax.device_get(flat),
        "memory": memory(jax),
    }


def leg_a(jax, dry: bool) -> dict:
    r = run_fl(jax, dry, rounds=4 if dry else 6, eval_every=2 if dry else 3)
    log(f"leg A: losses {[round(h['train_loss'], 4) for h in r['history']]} "
        f"test_acc {r['history'][-1]['test_acc']:.4f}")
    return r


# ---------------------------------------------------------------------------
# Leg C — every Pallas kernel, compiled, against its jnp reference
# ---------------------------------------------------------------------------

def leg_c(jax, dry: bool, ref_a: dict | None) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.ops.pallas import backend, noise, quantize as q

    interp = dry  # explicit, never derived: compiled on the chip
    check(backend.resolve_interpret(None) == dry,
          "kernels that derive `interpret` would not run compiled here")
    t0 = time.perf_counter()
    key = jax.random.PRNGKey(7)

    # quantize/dequantize: the ResNet-20 flat tree and a rank-8 LoRA factor
    flat = (ref_a["flat_model"] if ref_a is not None
            else np.asarray(jax.random.normal(key, (272_474,)) * 0.1))
    lora = np.asarray(jax.random.normal(key, (128, 8)) * 0.02).reshape(-1)
    for name, vec in (("flat_model", flat), ("lora_r8", lora)):
        x = jnp.asarray(vec, jnp.float32)
        values, scales, n = q.quantize_int8_stochastic(x, key, interpret=interp)
        v_ref, s_ref, n_ref = q.quantize_int8_reference(x, key)
        check(n == n_ref == x.shape[0], f"quantize[{name}] length")
        np.testing.assert_array_equal(np.asarray(values), np.asarray(v_ref),
                                      err_msg=f"quantize[{name}] values")
        np.testing.assert_allclose(np.asarray(scales), np.asarray(s_ref),
                                   rtol=1e-6, err_msg=f"quantize[{name}] scales")
        back = q.dequantize_int8(values, scales, n, interpret=interp)
        check(float(jnp.abs(back - x).max()) <= float(scales.max()) + 1e-6,
              f"dequantize[{name}] error exceeds one step")
        log(f"leg C: quantize/dequantize[{name}] {x.shape[0]} elements ok")

    x = jnp.asarray(flat, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(noise.apply_gaussian_noise(x, key, 0.37, interpret=interp)),
        np.asarray(noise.apply_gaussian_noise_reference(x, key, 0.37)),
        rtol=1e-6, atol=1e-6, err_msg="apply_gaussian_noise")
    log("leg C: apply_gaussian_noise ok")
    return {"kernels_s": time.perf_counter() - t0, "memory": memory(jax)}


# ---------------------------------------------------------------------------
# Legs D and E — a fused kernel under the module that calls it
# ---------------------------------------------------------------------------

def kernel_against_lax(jax, dry: bool, module_cls, cfg, sites, kernel_sites: dict, x, probe, *args):
    """``module_cls(cfg)`` must take its fused kernel (``sites()`` grows by
    ``kernel_sites``) and the same module holding a mesh its ``lax`` form,
    whatever the backend -> (sites taken, relative gaps of the output and of
    the input's gradient under ``probe``)."""
    import jax.numpy as jnp

    from fedml_tpu.parallel import mesh as meshlib

    params = jax.tree_util.tree_map(
        lambda p: p.astype(cfg.dtype), jax.jit(module_cls(cfg).init)(jax.random.PRNGKey(5), x, *args)["params"])

    def run(module):
        def both(x):
            y, back = jax.vjp(lambda x: module.apply({"params": params}, x, *args), x)
            return y, back(probe)[0]
        return jax.jit(both)(x)

    before = sites()
    got = run(module_cls(cfg))
    taken = {p: n - before[p] for p, n in sites().items()}
    want = run(module_cls(cfg, mesh=meshlib.make_mesh((meshlib.AXIS_DATA,), devices=jax.devices()[:1])))
    if not dry:
        check(jax.default_backend() == "tpu", f"backend is {jax.default_backend()}")
        check(taken == kernel_sites, f"the kernel was not the path taken: {taken}")
    return taken, [float(jnp.linalg.norm((g - w).astype(jnp.float32).ravel())
                         / jnp.linalg.norm(w.astype(jnp.float32).ravel())) for g, w in zip(got, want)]


def leg_d(jax, dry: bool) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.models.transformer import MLAttention, TransformerConfig
    from fedml_tpu.ops.sparse_attention import attention_sites

    t0 = time.perf_counter()
    seq = 128 if dry else 1024
    cfg = TransformerConfig(
        vocab_size=256, d_model=512, n_layers=1, n_heads=8, n_kv_heads=8, max_seq_len=seq,
        mixer_types=("mla",), q_lora_rank=256, kv_lora_rank=128, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, seq, cfg.d_model), cfg.dtype)
    probe = jax.random.normal(jax.random.PRNGKey(4), x.shape, cfg.dtype)
    pos = jnp.broadcast_to(jnp.arange(seq), (2, seq))
    taken, gaps = kernel_against_lax(jax, dry, MLAttention, cfg, attention_sites,
                                     {"kernel": 1, "blockwise": 0}, x, probe, pos)
    # bfloat16 probabilities rounded under other running maxima (tiles of
    # 1,024 against chunks of 512) differ by about 1e-3; a wrong tile by 1
    check(all(np.isfinite(gaps)) and max(gaps) < 1e-2,
          f"kernel against the lax pass: output and input gradient differ by {gaps}")
    log(f"leg D: MLAttention {seq} tokens x 8 heads x 192|128, sites {taken}, "
        f"gaps to the lax pass (output, input gradient) {gaps}")
    return {"kernels_s": time.perf_counter() - t0, "memory": memory(jax)}


def leg_e(jax, dry: bool) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.models.transformer import Mamba, TransformerConfig
    from fedml_tpu.ops.ssd import scan_sites

    t0 = time.perf_counter()
    seq, chunk = (256, 128) if dry else (2048, 256)
    cfg = TransformerConfig(
        vocab_size=256, d_model=256, n_layers=1, n_heads=4, n_kv_heads=4, max_seq_len=seq,
        mixer_types=("mamba",), mamba_heads=16, mamba_head_dim=64, mamba_d_state=128, mamba_chunk=chunk)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, seq, cfg.d_model), cfg.dtype)
    probe = jax.random.normal(jax.random.PRNGKey(4), x.shape, cfg.dtype)
    pos = jnp.broadcast_to(jnp.arange(seq), (2, seq))
    # documents that start inside chunks, and one that is a row's last token
    cuts = np.array([[seq // 3, seq // 2 + 5], [7, seq - 1]])
    segments = jnp.asarray((np.arange(seq)[None, :, None] >= cuts[:, None, :]).sum(-1), jnp.int32)
    taken, gaps = kernel_against_lax(jax, dry, Mamba, cfg, scan_sites, {"kernel": 1, "scan": 0},
                                     x, probe, pos, segments)
    # bfloat16 tiles cast before other products differ by about 3e-3; a wrong
    # reset or a wrong state by 0.1 and more
    check(all(np.isfinite(gaps)) and max(gaps) < 2e-2,
          f"kernel pair against the lax scan: output and input gradient differ by {gaps}")
    log(f"leg E: Mamba {seq} tokens x 16 heads x 64, state 128, chunk {chunk}, sites {taken}, "
        f"gaps to the lax scan (output, input gradient) {gaps}")
    return {"kernels_s": time.perf_counter() - t0, "memory": memory(jax)}


# ---------------------------------------------------------------------------
# Leg B — LLM train step (bench.py's llm shape)
# ---------------------------------------------------------------------------

def leg_b(jax, dry: bool, mesh_shape: dict | None, compiles: list) -> dict:
    import math

    import numpy as np

    from fedml_tpu.llm.train import LLMTrainArgs, LLMTrainer
    from fedml_tpu.models.transformer import TransformerConfig
    from fedml_tpu.parallel import mesh as meshlib

    if dry:
        tcfg = TransformerConfig.tiny(vocab_size=1024)
        args = LLMTrainArgs(batch_size=4, seq_len=128, total_steps=16, warmup_steps=1)
    else:
        tcfg = TransformerConfig(
            vocab_size=32000, d_model=2048, n_layers=8, n_heads=16, n_kv_heads=16,
            d_ff=5632, max_seq_len=2048, remat=True, remat_policy="dots")
        args = LLMTrainArgs(batch_size=8, seq_len=2048, total_steps=16, warmup_steps=1)
    mesh = None  # LLMTrainer's default: every device on the data axis
    if mesh_shape is not None:
        mesh = meshlib.make_mesh(tuple(mesh_shape), tuple(mesh_shape.values()))

    trainer = LLMTrainer(tcfg, args, mesh=mesh)
    check(trainer.mesh.devices.size == jax.device_count(),
          f"mesh {dict(trainer.mesh.shape)} does not span every device")
    check_spread(jax, (trainer.params, trainer.opt_state), "LLM placement")

    # one seeded batch, repeated: the loss must fall as the step memorises it
    tokens = np.random.RandomState(0).randint(
        0, tcfg.vocab_size, (args.batch_size, args.seq_len + 1)).astype(np.int32)

    def batches():
        while True:
            yield tokens[:, :-1], tokens[:, 1:]

    steps = 5
    history = trainer.fit(batches(), steps=1)
    after_first = len(compiles)
    history += trainer.fit(batches(), steps=steps - 1)
    check(len(history) == steps, f"{len(history)} of {steps} steps ran")
    for h in history:
        check(math.isfinite(h["loss"]), f"step {h['step']}: loss={h['loss']}")
    # the schedule warms up from lr 0, so step 2 is the first to see an update
    check(history[-1]["loss"] < history[0]["loss"],
          f"loss did not fall: {[h['loss'] for h in history]}")
    check(len(compiles) == after_first,
          f"{len(compiles) - after_first} trace/compile event(s) after the "
          "first step (the step must not recompile)")
    steady = sorted(h["step_time_s"] for h in history[1:])[len(history[1:]) // 2]
    log(f"leg B {dict(trainer.mesh.shape)}: {trainer.n_params() / 1e6:.0f}M params, "
        f"losses {[round(h['loss'], 3) for h in history]}")
    return {"compile_s": history[0]["step_time_s"] - steady, "steady_s": steady,
            "memory": memory(jax)}


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="tiny shapes + interpreted kernels on whatever "
                         "device is there; debugging only, labelled dry_run")
    dry = ap.parse_args().dry_run_cpu

    if not os.path.isdir(os.path.join(HERE, "fedml_tpu")):
        print(f"chip_smoke.py: no fedml_tpu package beside {__file__}; run it "
              "from the root of a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not dry:
        print(f"chip_smoke.py: no TPU — jax.devices() = {jax.devices()}; "
              "refusing to continue on another platform", file=sys.stderr)
        return 2

    import importlib.metadata as md

    import jaxlib

    from fedml_tpu.core.cache import setup_persistent_cache
    from fedml_tpu.ops import flops as flopslib

    tag = "dry_run " if dry else ""
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    log(f"{tag}platform={device['platform']} device_kind={device['kind']!r} "
        f"count={device['count']} jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu}")
    log(f"{tag}compile cache: {setup_persistent_cache()}")
    # an unknown TPU kind raises here (ops/flops.py): no MFU without a peak
    log(f"{tag}peak bf16 FLOP/s per chip: {flopslib.device_peak_flops(dev)}")

    # every trace and every backend compile (persistent-cache hits included)
    # from here on: Leg B reads it to prove its second step reuses the first's
    compiles: list = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if name.endswith(("jaxpr_trace_duration", "backend_compile_duration"))
        else None)

    n = device["count"]
    legs = [("A", lambda: leg_a(jax, dry)),
            ("C", lambda: leg_c(jax, dry, results.get("A"))),
            ("D", lambda: leg_d(jax, dry)),
            ("E", lambda: leg_e(jax, dry)),
            ("B", lambda: leg_b(jax, dry, None, compiles))]
    if n >= 4 and n % 2 == 0:
        legs.append(("B data x model", lambda: leg_b(
            jax, dry, {"data": n // 2, "model": 2}, compiles)))
    results, failed = {}, []
    for name, leg in legs:
        t0 = time.perf_counter()
        try:
            results[name] = r = leg()
            if "kernels_s" in r:
                took = f"kernels {r['kernels_s']:.1f}s"
            else:
                per = f"{r['chunk_rounds']}-round chunk" if "chunk_rounds" in r else "step"
                took = f"compile {r['compile_s']:.1f}s, steady {r['steady_s']:.3f}s per {per}"
            log(f"{tag}leg {name} PASSED in {time.perf_counter() - t0:.1f}s: {took}, "
                f"memory in-use/peak per device: {fmt_memory(r['memory'])}")
        except Exception:
            failed.append(name)
            traceback.print_exc()
            log(f"{tag}leg {name} FAILED after {time.perf_counter() - t0:.1f}s")
        gc.collect()  # drop the leg's device buffers before the next one

    out = {"ok": not failed, "device": device}
    if dry:
        out["dry_run"] = True
    if failed:
        out["failed_legs"] = failed
    print(json.dumps(out), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
