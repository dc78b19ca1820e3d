"""Readers of the per-layer metrics of the two MiniCPM-SALA mixers (reader
spec ``sala_readers:<function>``).

The window's device trace cannot yet give time by named scope
(``bench_trace.load_events`` drops the HLO metadata), so each kind of mixer is
run ALONE here, under a device trace of its own: the program's own function
(``ops/lightning_attention.py``, ``ops/sparse_attention.py``) at the cell's
shapes on random bfloat16 q, k, v, differentiated under ``jax.checkpoint``
with the policy the step's blocks have, so that it holds what a block holds of
it: the forward, the rematerialised forward and the backward.  The time is the
sum of the device ops' durations over the traced calls, from
``bench_trace``; no host clock enters.  A reader runs only in a traced run and
only after the window and the check, so set-up and the window pay nothing.  A
program without these mixers makes every reader here return ``None``.
"""

from __future__ import annotations

import os
import shutil

import bench_trace
import flops_sala

KINDS = {"lightning": "lightning-attn", "sparse": "minicpm4"}
WARM_CALLS, TRACED_CALLS = 2, 5
_alone: dict = {}


def _mixer_step(cfg, kind: str):
    """Jitted gradient of one mixer alone with respect to q, k, v, under the
    remat policy of the step's blocks."""
    import jax
    import jax.numpy as jnp
    from fedml_tpu.models.transformer import block_remat_policy
    from fedml_tpu.ops.lightning_attention import decay_slopes, lightning_attention
    from fedml_tpu.ops.sparse_attention import sparse_attention

    if kind == "lightning-attn":
        fn = lambda q, k, v: lightning_attention(q, k, v, decay_slopes(q.shape[2]))
    else:
        fn = lambda q, k, v: sparse_attention(q, k, v, **cfg.sparse_selection)[0]
    if cfg.remat:
        fn = jax.checkpoint(fn, policy=block_remat_policy(cfg))
    return jax.jit(jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2),
                            argnums=(0, 1, 2)))


def alone_device_s(ctx, mixer: str):
    """Device seconds one call of ``mixer`` alone takes at the cell's shapes
    (see the module's docstring), or ``None`` where the program has no such
    mixer.  Measured once a run."""
    if mixer in _alone:
        return _alone[mixer]
    try:
        import jax
        import jax.numpy as jnp
        import sala

        t = ctx["traffic"]
        cfg = sala.transformer_config(ctx["config"], t["seq_len"], t.get("remat_policy", "dots"),
                                      **t.get("program", {}))
        step = _mixer_step(cfg, KINDS[mixer])
    except (ImportError, TypeError, KeyError):
        return None
    h, kv, hd = flops_sala.mixer_heads(ctx["config"], KINDS[mixer])
    key = jax.random.key(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (t["batch_size"], t["seq_len"], n, hd),
                                 jnp.bfloat16) for i, n in enumerate((h, kv, kv)))
    for _ in range(WARM_CALLS):
        jax.block_until_ready(step(q, k, v))
    trace_dir = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                             ".bench_trace", f"alone.{mixer}.{os.getpid()}")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        for _ in range(TRACED_CALLS):
            jax.block_until_ready(step(q, k, v))
    finally:
        jax.profiler.stop_trace()
    try:
        events = bench_trace.load_events(bench_trace.find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    took = sum(bench_trace.op_seconds(events).values()) / TRACED_CALLS if events else 0.0
    _alone[mixer] = took if took > 0 else None
    return _alone[mixer]


def mixer_step_share(ctx, args):
    """% of a step's device time that this kind of mixer's layers take at the
    device time one takes alone (projections, norms and gates left out)."""
    busy, steps = ctx.get("busy"), ctx["window"].get("attempted")
    if not busy or busy["busy_s"] <= 0 or not steps:
        return None
    took = alone_device_s(ctx, args["mixer"])
    if took is None:
        return None
    c = ctx["config"]
    layers = c["mixer_types"][: c["num_hidden_layers"]].count(KINDS[args["mixer"]])
    return 100.0 * took * layers / (busy["busy_s"] / steps)


def mixer_roofline(ctx, args):
    """Least time the chip could take for the mixer's required work (the
    larger of FLOPs over peak and least bytes over HBM peak; forward and
    backward once, no remat) over the device time it takes alone."""
    if not ctx.get("peaks"):
        return None
    took = alone_device_s(ctx, args["mixer"])
    if took is None:
        return None
    t = ctx["traffic"]
    flops, moved = flops_sala.mixer_work(ctx["config"], KINDS[args["mixer"]],
                                         t["batch_size"], t["seq_len"])
    need = max(flops / ctx["peaks"]["bf16_flops"], moved / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * need / took if need > 0 else None
