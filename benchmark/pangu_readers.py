"""Readers of the per-layer metrics of the openPangu-Ultra-MoE cell's two new
layers (reader spec ``pangu_readers:<function>``).

As ``sala_readers.py`` does, and for its reason (the window's device trace
gives no time by named scope yet), each part is run ALONE under a device
trace of its own: the program's own module (``MLAttention`` whole, with its five
projections; ``MoE`` whole, with its router, its shared expert and the held
experts) at the cell's shapes on a random bfloat16 input and random bfloat16
kernels, differentiated with respect to its input under ``jax.checkpoint``
with the policy the step's blocks have, so that it holds what a block holds of
it: the forward, the rematerialised forward and the backward to the
activations (the kernels are frozen).  The time is the sum of the device ops'
durations over the traced calls; no host clock enters.  A reader runs only in
a traced run and only after the window and the check.  A program without
these modules makes every reader here return ``None``.
"""

from __future__ import annotations

import os
import shutil

import bench_trace
import flops
import flops_pangu

WARM_CALLS, TRACED_CALLS = 2, 5
_alone: dict = {}


def _module_step(cfg, part: str, batch: int, seq: int):
    """(jitted gradient of ``part`` alone with respect to its input, under
    the block's remat; its random input and kernels; what it sowed)."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.models import transformer as tfm

    positions = jnp.broadcast_to(jnp.arange(seq), (batch, seq))
    if part == "mla":
        module, args = tfm.MLAttention(cfg), (positions,)
    else:
        module, args = tfm.MoE(cfg), ()
    x = jax.random.normal(jax.random.key(0), (batch, seq, cfg.d_model), jnp.bfloat16)
    params = jax.jit(lambda: jax.tree_util.tree_map(
        lambda p: p.astype(cfg.dtype), module.init(jax.random.key(1), x, *args)["params"]))()

    def fn(params, x):
        return module.apply({"params": params}, x, *args, mutable=["stats"])

    if cfg.remat:
        fn = jax.checkpoint(fn, policy=tfm.block_remat_policy(cfg))

    def loss(x, params):
        y, sown = fn(params, x)
        return jnp.sum(y.astype(jnp.float32) ** 2), sown.get("stats", {})

    return jax.jit(jax.grad(loss, has_aux=True)), x, params


def alone(ctx, part: str):
    """(device seconds one call of ``part`` alone takes at the cell's shapes,
    what the module sowed), or ``None`` where the program has no such module.
    Measured once a run."""
    if part in _alone:
        return _alone[part]
    try:
        import jax
        import pangu

        t = ctx["traffic"]
        cfg = pangu.transformer_config(ctx["config"], t["seq_len"], t.get("remat_policy", "full"),
                                       **t.get("program", {}))
        step, x, params = _module_step(cfg, part, t["batch_size"], t["seq_len"])
    except (ImportError, TypeError, KeyError, AttributeError):
        return None
    for _ in range(WARM_CALLS):
        _, sown = jax.block_until_ready(step(x, params))
    trace_dir = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                             ".bench_trace", f"alone.{part}.{os.getpid()}")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        for _ in range(TRACED_CALLS):
            jax.block_until_ready(step(x, params))
    finally:
        jax.profiler.stop_trace()
    try:
        events = bench_trace.load_events(bench_trace.find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    took = sum(bench_trace.op_seconds(events).values()) / TRACED_CALLS if events else 0.0
    _alone[part] = (took, {k: float(v) for k, v in sown.items()}) if took > 0 else None
    return _alone[part]


def _blocks(ctx, part: str) -> int:
    blocks = flops_pangu.blocks(ctx["config"])
    return len(blocks) if part == "mla" else sum(experts for _, experts in blocks)


def part_step_share(ctx, args):
    """% of a step's device time that this part's blocks take at the device
    time one takes alone."""
    busy, steps = ctx.get("busy"), ctx["window"].get("attempted")
    if not busy or busy["busy_s"] <= 0 or not steps:
        return None
    got = alone(ctx, args["part"])
    if got is None:
        return None
    return 100.0 * got[0] * _blocks(ctx, args["part"]) / (busy["busy_s"] / steps)


def part_roofline(ctx, args):
    """Least time the chip could take for the part's required work (per
    product the larger of FLOPs over peak and least bytes over HBM peak,
    summed; forward and the gradient to activations once, no remat) over the
    device time it takes alone.  The held experts' products are counted over
    the rows REALLY routed to them in that run (what the module sowed)."""
    if not ctx.get("peaks"):
        return None
    got = alone(ctx, args["part"])
    if got is None:
        return None
    took, sown = got
    c, t = ctx["config"], ctx["traffic"]
    if args["part"] == "mla":
        work = [flops_pangu.mla_work(c, t["batch_size"], t["seq_len"])]
    else:
        work = flops_pangu.moe_products(c, t["batch_size"] * t["seq_len"], sown["moe_held"])
    need = flops.roofline_seconds(work, ctx["peaks"]["bf16_flops"], ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * need / took if need > 0 else None


def load_max_over_mean(ctx, args):
    """The busiest held expert's tokens over the held experts' mean, summed
    over the expert layers and the window's steps (1 = even)."""
    import program_spans

    pct = program_spans.window_attr_ratio(ctx, {"span": "llm.step", "num": "moe_max_load",
                                                "den": "moe_held"})
    return None if pct is None else pct / 100.0 * ctx["config"]["n_routed_experts"]
