"""Readers of the per-layer metrics of the Granite 4.0-H cell's new layers
(reader spec ``granite_readers:<function>``).

As ``sala_readers.py`` and ``pangu_readers.py`` do, and for their reason (the
window's device trace gives no time by named scope yet), each part is run
ALONE under a device trace of its own, after the window and the check, in
traced runs only: the program's own code at the cell's shapes on random
bfloat16 inputs, on a row packed with the traffic's documents in the listed
order, differentiated with respect to its inputs under ``jax.checkpoint`` with
the policy the step's blocks have, so that it holds what a block holds of it:
the forward, the rematerialised forward and the backward.

``mamba``      the module whole (``Mamba``: in_proj, convolution, scan, gated norm, out_proj)
``ssd``        the selective scan alone (``ops/ssd.ssd``) on x, dt, B, C
``conv``       the depthwise convolution with its silu alone (``causal_conv``)
``attention``  the module whole (``Attention``: wq wk wv, the blockwise
               attention on whatever path it takes, wo)

The time is the sum of the device ops' durations over the traced calls; no
host clock enters.  A program without these parts makes every reader here
return ``None``.
"""

from __future__ import annotations

import os
import shutil

import bench_trace
import flops
import flops_granite

WARM_CALLS, TRACED_CALLS = 2, 5
_alone: dict = {}


def _part_step(cfg, part: str, batch: int, lengths):
    """(jitted gradient of ``part`` alone under the block's remat, its inputs)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from fedml_tpu.models import transformer as tfm
    from fedml_tpu.ops.ssd import ssd

    seq, key, bf16 = sum(lengths), jax.random.key(0), jnp.bfloat16
    segments = jnp.asarray(np.tile(np.repeat(np.arange(1, len(lengths) + 1, dtype=np.int32), lengths),
                                   (batch, 1)))
    normal = lambda i, *shape: jax.random.normal(jax.random.fold_in(key, i), shape, bf16)
    h, p, g, n = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_groups, cfg.mamba_d_state
    if part in ("mamba", "attention"):
        module = (tfm.Mamba if part == "mamba" else tfm.Attention)(cfg)
        positions = jnp.broadcast_to(jnp.arange(seq), (batch, seq))
        x = normal(0, batch, seq, cfg.d_model)
        params = jax.jit(lambda: jax.tree_util.tree_map(
            lambda t: t.astype(cfg.dtype), module.init(jax.random.key(1), x, positions, segments)["params"]))()
        fn = lambda x: module.apply({"params": params}, x, positions, segments)
        inputs = (x,)
    elif part == "ssd":
        a = -jnp.arange(1, h + 1, dtype=jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 9), (batch, seq, h)) - 4.0)
        fn = lambda x, b_in, c_in: ssd(x, dt, a, b_in, c_in, jnp.ones((h,)), segments, cfg.mamba_chunk)
        inputs = (normal(0, batch, seq, h, p), normal(1, batch, seq, g, n), normal(2, batch, seq, g, n))
    else:
        width = h * p + 2 * g * n
        kernel, bias = normal(1, cfg.mamba_d_conv, width), normal(2, width)
        fn = lambda x: jax.nn.silu(tfm.causal_conv(x, kernel, bias, segments)).astype(bf16)
        inputs = (normal(0, batch, seq, width),)
    if cfg.remat:
        fn = jax.checkpoint(fn, policy=tfm.block_remat_policy(cfg))
    grad = jax.grad(lambda *xs: jnp.sum(fn(*xs).astype(jnp.float32) ** 2), argnums=tuple(range(len(inputs))))
    return jax.jit(grad), inputs


def alone(ctx, part: str):
    """Device seconds one call of ``part`` alone takes at the cell's shapes,
    or ``None`` where the program has no such part.  Measured once a run."""
    if part in _alone:
        return _alone[part]
    try:
        import jax
        import granite

        t = ctx["traffic"]
        cfg = granite.transformer_config(ctx["config"], t["seq_len"], t.get("remat_policy", "full"),
                                         **t.get("program", {}))
        step, inputs = _part_step(cfg, part, t["batch_size"], t["doc_lengths"])
    except (ImportError, TypeError, KeyError, AttributeError):
        return None
    for _ in range(WARM_CALLS):
        jax.block_until_ready(step(*inputs))
    trace_dir = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                             ".bench_trace", f"alone.{part}.{os.getpid()}")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        for _ in range(TRACED_CALLS):
            jax.block_until_ready(step(*inputs))
    finally:
        jax.profiler.stop_trace()
    try:
        events = bench_trace.load_events(bench_trace.find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    took = sum(bench_trace.op_seconds(events).values()) / TRACED_CALLS if events else 0.0
    _alone[part] = took if took > 0 else None
    return _alone[part]


def _layers(ctx, part: str) -> int:
    kinds = flops_granite.kinds(ctx["config"])
    return kinds.count("attention") if part == "attention" else kinds.count("mamba")


def part_step_share(ctx, args):
    """% of a step's device time that this part's layers take at the device
    time one takes alone."""
    busy, steps = ctx.get("busy"), ctx["window"].get("attempted")
    if not busy or busy["busy_s"] <= 0 or not steps:
        return None
    took = alone(ctx, args["part"])
    if took is None:
        return None
    return 100.0 * took * _layers(ctx, args["part"]) / (busy["busy_s"] / steps)


def part_roofline(ctx, args):
    """Least time the chip could take for the part's required work
    (``flops_granite.part_work``: per product the larger of FLOPs over peak
    and least bytes over HBM peak, summed; forward and backward once, no
    remat) over the device time it takes alone."""
    if not ctx.get("peaks"):
        return None
    took = alone(ctx, args["part"])
    if took is None:
        return None
    t = ctx["traffic"]
    work = flops_granite.part_work(ctx["config"], args["part"], t["batch_size"], t["doc_lengths"])
    need = flops.roofline_seconds(work, ctx["peaks"]["bf16_flops"], ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * need / took if need > 0 else None
